"""chip_smoke.py off the chip: it must refuse a CPU, and each of its phases
must run end to end at a tiny size on the CPU mesh (on-chip-measurement
guide §1: debug here small, send the same code there at the real size).
Plus the two decisions the smoke path leans on: where the compile cache
lives, and that an unknown chip is an error."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from horovod_tpu.models import ResNetTiny  # noqa: E402
from horovod_tpu.models.bert import bert_tiny  # noqa: E402
from horovod_tpu.models.llama import llama_tiny  # noqa: E402
from horovod_tpu.tools.perf import _peak_for_kind  # noqa: E402


_CACHE_PROBE = ("import jax, horovod_tpu; "
                "print(jax.config.jax_compilation_cache_dir)")


def _spawn(args, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(env_extra, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen([sys.executable, *args], env=env, cwd=REPO,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


@pytest.fixture(scope="module")
def fresh_interpreters(tmp_path_factory):
    """Four fresh interpreters at once (each pays the jax import): the
    smoke script itself, two cache probes with the env var unset, one with
    it set."""
    mine = str(tmp_path_factory.mktemp("cache"))
    procs = {
        "smoke": _spawn([os.path.join(REPO, "chip_smoke.py")], {}),
        "unset_a": _spawn(["-c", _CACHE_PROBE], {}),
        "unset_b": _spawn(["-c", _CACHE_PROBE], {}),
        "set": _spawn(["-c", _CACHE_PROBE],
                      {"JAX_COMPILATION_CACHE_DIR": mine}),
    }
    out = {"set_dir": mine}
    for name, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=120)
        out[name] = (proc.returncode, stdout, stderr)
    return out


def test_refuses_a_cpu_and_prints_no_result(fresh_interpreters):
    rc, stdout, stderr = fresh_interpreters["smoke"]
    assert rc != 0
    assert "'cpu'" in stderr and "needs a TPU" in stderr
    assert '"ok": true' not in stdout


def test_compile_cache_placement(fresh_interpreters):
    # env unset: <checkout>/.jax_cache, the same in every fresh interpreter
    for name in ("unset_a", "unset_b"):
        rc, stdout, stderr = fresh_interpreters[name]
        assert rc == 0, stderr
        assert stdout.strip() == os.path.join(REPO, ".jax_cache")
    # env set: jax reads it itself and the package leaves it alone
    rc, stdout, stderr = fresh_interpreters["set"]
    assert rc == 0, stderr
    assert stdout.strip() == fresh_interpreters["set_dir"]


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_unknown_chip_is_an_error_not_nan():
    assert _peak_for_kind("TPU v5 lite") == 197e12
    dev = chip_smoke.require_chip([_Dev("tpu", "TPU v5 lite")])
    assert dev == {"platform": "tpu", "device_kind": "TPU v5 lite",
                   "n_devices": 1}
    with pytest.raises(SystemExit, match="_PEAK_TABLE"):
        chip_smoke.require_chip([_Dev("tpu", "TPU v9000")])
    with pytest.raises(SystemExit, match="'cpu'"):
        chip_smoke.require_chip(jax.devices())


# ---------------------------------------------- phases, tiny, on the CPU mesh

_F32 = {"dtype": "float32", "tol_fwd": 1e-4, "tol_grad": 1e-3}


def test_phase_train_resnet_tiny():
    facts = chip_smoke.phase_train_resnet({
        "model": lambda axis: ResNetTiny(num_classes=10, axis_name=axis,
                                         dtype=jnp.float32),
        "image": 8, "classes": 10, "per_chip_batch": 2, "steps": 4,
        "seed": 0, "slope_scan_lengths": (1, 2), "slope_rounds": 1})
    assert facts["global_batch"] == 16 and facts["param_device_span"] == 8
    assert 8 in facts["allreduce_group_sizes"] and facts["donated"]
    assert facts["loss_last"] < facts["loss_first"]
    json.dumps(facts)


def test_phase_train_bert_tiny():
    facts = chip_smoke.phase_train_bert({
        "model_cfg": bert_tiny(), "seq": 32, "per_chip_batch": 1,
        "steps": 3, "seed": 1, "min_tpu_custom_calls": 0})
    assert facts["tpu_custom_calls"] == 0      # no Mosaic off the chip
    with pytest.raises(AssertionError, match="Mosaic"):
        chip_smoke._require_mosaic(facts, 1, "train-bert")
    json.dumps(facts)


def test_phase_kernels_tiny():
    facts = chip_smoke.phase_kernels({
        "flash": [
            {"shape": (1, 64, 2, 32), "causal": True, "masked": False,
             "residuals": False, "seed": 2, **_F32},
            {"shape": (2, 48, 2, 32), "causal": False, "masked": True,
             "residuals": True, "seed": 3, **_F32}],
        "combine_sizes": [(1 << 16) + 77], "combine_tol": 1e-4})
    assert len(facts["flash_attention"]) == 2
    assert "merged_o" in facts["flash_attention"][1]["max_abs_err"]
    json.dumps(facts)


def test_phase_adasum_tiny():
    facts = chip_smoke.phase_adasum({
        "vector": 1 << 10, "tol": 1e-4, "seed": 5,
        "model_cfg": llama_tiny(), "seq": 16, "per_chip_batch": 1,
        "steps": 3, "min_tpu_custom_calls_multi": 0})
    assert facts["butterfly_rounds"] == 3
    assert facts["collective_permutes"] >= 3
    json.dumps(facts)


def test_phase_serve_tiny():
    facts = chip_smoke.phase_serve({
        "model_note": "llama_tiny",
        "model_cfg": dataclasses.replace(llama_tiny(), max_seq_len=64),
        "block_size": 8, "prefill_buckets": (16, 32), "slots": 4,
        "wave": 4, "max_new": 4, "check_prompt_len": 20,
        "logit_tol": 1e-4, "request_timeout_s": 120.0, "seed": 6})
    assert facts["compile_counts"] == {"decode": 1, "prefill": 2}
    json.dumps(facts)


def test_full_configs_are_the_sizes_the_issue_names():
    full = chip_smoke.full_configs()
    assert [name for name, _ in chip_smoke.PHASES] == list(full)
    assert full["train-resnet"]["per_chip_batch"] == 128
    bert = full["train-bert"]
    assert (bert["model_cfg"].n_layers, bert["model_cfg"].dim,
            bert["seq"], bert["per_chip_batch"]) == (24, 1024, 512, 8)
    llama = full["serve"]["model_cfg"]
    assert (llama.dim, llama.n_heads, llama.n_kv_heads, llama.hidden_dim,
            llama.vocab_size) == (4096, 32, 8, 14336, 128256)
