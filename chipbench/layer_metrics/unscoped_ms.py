"""Device time a step in leaf operations that carry none of the step
program's phase scopes in its own map (``chipbench/scopes.py``): the
waits of asynchronous copies and slices (``copy-done``, ``slice-done``),
which XLA makes and names nothing. With the four phase readers (and the
exchange's three over several chips) it sums to the busy time a step."""
from chipbench import phases, scopes

UNIT = "ms"
LAYER = "step program"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.available()


def read(run):
    return scopes.of(run).phase_ms[phases.OTHER]
