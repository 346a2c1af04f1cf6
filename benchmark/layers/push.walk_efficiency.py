"""push.walk_efficiency: percent of the arcs the push engine walks that
are arcs of reached vertices: twice the window's useful edges (Graph500's
tuples in the root's component, each stored both ways) over the arcs the
engine's counter ``push.arcs_walked`` says the window's traversals
walked.  A dense round walks every arc whatever the frontier, so this is
what a direction switch or a bottom-up round moves.

The counter also holds the warm-up traversal, run before the window
from the same root as every traversal of the cell: the window's walked
arcs are its total less one traversal's share.  None without a device
trace, outside BFS, or where the program keeps no such counter."""


def read(run):
    if run.summary is None or run.app != "bfs":
        return None
    try:
        from lux_tpu.obs import counters
    except ImportError:
        return None
    calls, walked = counters("push.arcs_walked").get(
        "push.arcs_walked", (0, 0))
    if calls < 2 or not walked:
        return None
    return 100.0 * 2 * run.edges / (walked * (calls - 1) / calls)
