"""gteps: useful edges of every job of the window, over the seconds from
the window's start to the end of its last job (host clock), in 1e9 per
second.  Useful edges are the app's own count (apps/<app>.work), the
same whatever method the program uses."""


def read(run):
    return run.edges / run.window_s / 1e9
