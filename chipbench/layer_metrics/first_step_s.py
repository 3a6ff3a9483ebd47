"""Host seconds from the first ``step(...)`` call to its loss: tracing,
lowering, then compiling or loading from the persistent cache, and one step."""

UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"


def applies(cell):
    return True


def read(run):
    return run.first_step_s
