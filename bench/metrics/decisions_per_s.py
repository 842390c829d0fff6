"""Algorithm-1 decision points of every study completed in the window, over
the window's wall time."""


def read(run):
    return run.work["decisions"] / run.window_s
