"""setup_s: seconds from process start to the start of the window,
less the data set's generation (host clock)."""


def read(run):
    return run.setup_s
