"""push.exchange_bytes: bytes one chip receives over ICI in a BFS
traversal, from the push engine's own counter (``push.exchange_bytes``,
one call a traversal): the other chips' frontier queues every round and
their level state every dense round.  The counter's total over its
calls, for every traversal of the run, the warm-up job's included (the
cell traverses from one root, so every traversal moves the same bytes).
Read in traced runs only, as every per-layer metric; None outside BFS
or where the program keeps no such counter."""


def read(run):
    if run.app != "bfs":
        return None
    try:
        from lux_tpu.obs import counters
    except ImportError:
        return None
    calls, total = counters("push.exchange_bytes").get(
        "push.exchange_bytes", (0, 0))
    return total / calls if calls else None
