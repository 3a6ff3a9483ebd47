"""Device time a step spends in collective operations that nothing else on
the core covers: on device 0's ``XLA Ops`` line (leaf operations, one at a
time) the all-reduce, all-gather, reduce-scatter, collective-permute and
all-to-all operations, with their ``-start``/``-done`` halves, less any leaf
operation that overlaps them. ``Async XLA Ops`` spans are transfers in
flight behind compute and are not counted."""
from chipbench import trace

UNIT = "ms"
LAYER = "collectives"
MOVES = "samples_per_s_chip"


def applies(cell):
    return cell["chips"] > 1


def read(run):
    return 1e3 * run.trace.exposed_seconds_per_step(trace.is_collective) \
        or None
