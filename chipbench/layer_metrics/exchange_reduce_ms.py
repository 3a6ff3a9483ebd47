"""Device time a step in the gradient exchange's reduction: the leaf operations
on device 0 under ``hvd_exchange_reduce`` in the step program's own map
(``chipbench/scopes.py``): the all-reduces, the ``psum``s
and the averaging multiply. Less ``collective_exposed_ms`` it is the part
of the exchange that other work hides."""
from chipbench import scopes

UNIT = "ms"
LAYER = "collectives"
MOVES = "samples_per_s_chip"


def applies(cell):
    return cell["chips"] > 1 and scopes.available()


def read(run):
    return scopes.of(run).phase_ms["exchange_reduce"]
