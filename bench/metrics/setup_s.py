"""Process start to the first timed call, compiles and warm-up included."""


def read(run):
    return run.setup_s
