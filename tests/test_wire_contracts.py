"""Wire-contract drivers (VERDICT r5 #6 / ISSUE 14 / ISSUE 16 → ISSUE 17).

The topology × payload × hop-count invariants these tests used to spell
out inline now live in the contract registry
(``horovod_tpu/analysis/contracts.py``), declared once and checked both
here and by ``python -m horovod_tpu.analysis --contracts``:

- **adasum-butterfly**: log₂(n) permute rounds, FULL working buffer,
  XOR-partner topology (collectives/adasum.py);
- **ring-attention**: exactly the K and V shards rotate the +1 ring,
  nothing else rides the step (parallel/ring.py);
- **pipeline-handoff**: ONE activation permute per schedule tick,
  stage i → i+1 (parallel/pipeline.py);
- **decode-tp / verify-tp / prefill-tp** (tp ∈ {1, 2, 4}) and
  **decode-tp8 / verify-tp8** (llama + mixtral at tp = 8): exactly
  ``2·n_layers`` activation all-reduces over the full tp group — zero
  permutes, zero resharding (models/decode.py).

Builds are memoized in the registry, so these drivers and the full
``--contracts`` matrix (tests/test_contracts.py) share one lowering per
family per pytest process.
"""

import pytest

from horovod_tpu.analysis import contracts
from wire_accounting import collective_wire_costs


@pytest.mark.parametrize("family", [
    "adasum-butterfly", "ring-attention", "pipeline-handoff",
    "decode-tp", "verify-tp", "prefill-tp", "decode-tp8", "verify-tp8",
])
def test_wire_contract(family):
    findings = contracts.check_family(family)
    assert not findings, "\n".join(f.format() for f in findings)


def test_permute_parse_single_pair():
    """The tensor<1x2xi64> single-pair rendering parses too (a 2-device
    permute or a single handoff prints without nested brackets)."""
    hlo = '''
    %0 = "stablehlo.collective_permute"(%arg0) <{channel_handle =
      #stablehlo.channel_handle<handle = 1, type = 0>,
      source_target_pairs = dense<[[0, 1]]> : tensor<1x2xi64>}> :
      (tensor<4x2xf32>) -> tensor<4x2xf32>
    '''.replace("\n      ", " ")
    perms = [c for c in collective_wire_costs(hlo)
             if c["op"] == "collective_permute"]
    assert len(perms) == 1
    assert perms[0]["pairs"] == [[0, 1]]
    assert perms[0]["n_links"] == 1
    assert perms[0]["operand_bytes"] == 32
