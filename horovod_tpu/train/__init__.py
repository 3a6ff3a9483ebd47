"""Training harness package (formerly the single ``train.py`` module —
same public surface, re-exported here).

- ``step_builder`` — the composable step-program builder: ONE
  implementation of the two-program donation/DCE trick, the host-side
  dispatcher (cadence deferral + sentinel containment), scan folding,
  microbatch gradient accumulation, and the pipeline-parallel step.
- ``dp`` — the shard_map data-parallel step (reference-parity path).
- ``gspmd`` — the sharding-annotation path (dp/fsdp/sp/tp/ep).

See docs/train_step.md for the feature lattice: which combinations
produce which jitted programs, and why donation survives each.
"""

from .step_builder import (PipelineTrainState, accumulate_gradients,
                           build_program_set, create_pipeline_train_state,
                           export_decode_params, fold_scan, make_dispatch,
                           make_pipeline_train_step)
from .dp import TrainState, create_train_state, make_train_step
from .gspmd import (GSPMDTrainState, create_gspmd_train_state,
                    gspmd_shardings, make_gspmd_deferred_train_step,
                    make_gspmd_train_step, next_token_loss, rules_for_mesh)

__all__ = [
    "PipelineTrainState",
    "accumulate_gradients",
    "build_program_set",
    "create_pipeline_train_state",
    "export_decode_params",
    "fold_scan",
    "make_dispatch",
    "make_pipeline_train_step",
    "TrainState",
    "create_train_state",
    "make_train_step",
    "GSPMDTrainState",
    "create_gspmd_train_state",
    "gspmd_shardings",
    "make_gspmd_deferred_train_step",
    "make_gspmd_train_step",
    "next_token_loss",
    "rules_for_mesh",
]
