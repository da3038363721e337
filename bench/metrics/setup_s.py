"""Process start to the window's first timed operation: loading, weights,
warm-up of every shape the cell uses, and compiling in a cold checkout."""


def read(run):
    return run.setup_s
