"""push.dense_rounds: dense rounds a BFS traversal takes, from the push
engine's own counters (``push.dense_rounds``, one call a traversal):
their total over their calls, for every traversal of the run, the
warm-up job's included (the cell traverses from one root, so every
traversal takes the same rounds).  Read beside the device trace it
explains; None without one, or where the program keeps no such
counter."""


def read(run):
    if run.summary is None or run.app != "bfs":
        return None
    try:
        from lux_tpu.obs import counters
    except ImportError:
        return None
    calls, total = counters("push.dense_rounds").get(
        "push.dense_rounds", (0, 0))
    return total / calls if calls else None
