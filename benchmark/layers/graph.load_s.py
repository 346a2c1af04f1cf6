"""graph.load_s: host seconds of the program's graph load in set-up:
``read_lux``, the shard build and the placement on the device (the
benchmark's ``graph.load`` span around those calls)."""


def read(run):
    return run.spans.get("graph.load")
