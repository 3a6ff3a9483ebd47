"""Host seconds in ``create_train_state`` until the state is on the device."""

UNIT = "s"
LAYER = "state"
MOVES = "setup_s"


def applies(cell):
    return True


def read(run):
    return run.state_init_s
