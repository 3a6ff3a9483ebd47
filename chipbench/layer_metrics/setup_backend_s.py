"""Host seconds of set-up inside the backend's compiler or its persistent
cache: the library's ``compile.backend`` spans (JAX's own
``backend_compile_duration`` events, which include a cache load) that end
before the window opens."""
from chipbench import spans

UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"


def applies(cell):
    return spans.available()


def read(run):
    return spans.before_window(("compile.backend",))
