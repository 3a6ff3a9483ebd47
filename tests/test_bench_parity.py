"""Bench-parity regression tests (VERDICT r5 Weak #1 → ISSUE 17).

A driver reading of ``vs_baseline`` 0.9631 once fell outside the stated
±0.02 band. The bisect suspicion was that the r5 train.py deferral change
(``make_gspmd_deferred_train_step``) taxed ``make_train_step``. The
graph-level facts that rule that out permanently are now declared in
the contract registry (``horovod_tpu/analysis/contracts.py``) and
driven thin from here:

1. ``bench-arms-parity``: bench.py's two arms (hvd DistributedOptimizer
   step vs plain step) compile to programs with byte-identical — and on
   the bench's 1-device mesh, EMPTY — collective-op sets; any measured
   ratio shift is NOISE, not graph tax.
2. ``gspmd-deferred-every1``: the deferred factory at ``every=1`` emits
   collective HLO signature-identical to the standard GSPMD step it
   wraps — the deferral is graph-level inert at k=1.

Collective HLO is compared post-SPMD-partitioning (``.compile()``):
GSPMD inserts collectives during partitioning, so stablehlo lowering
alone would compare nothing.  Builds are memoized in the registry and
shared with the full ``--contracts`` matrix (tests/test_contracts.py).
"""

from __future__ import annotations

import pytest

from horovod_tpu.analysis import contracts


@pytest.mark.parametrize("family", ["bench-arms-parity",
                                    "gspmd-deferred-every1"])
def test_bench_parity_contract(family):
    findings = contracts.check_family(family)
    assert not findings, "\n".join(f.format() for f in findings)
