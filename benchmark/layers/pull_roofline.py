"""pull_roofline: the pull engine's share of the HBM roofline, in
percent: the useful bytes of the window's PageRank jobs (a floor counted
from the graph's sizes, apps/pagerank.work) over the device's busy time
(the mean over the cell's chips) times the peak bandwidth of all its
chips (peaks.py)."""
from benchmark import peaks


def read(run):
    if run.summary is None or run.app != "pagerank":
        return None
    return 100.0 * run.useful_bytes / (
        run.summary.busy_s * peaks.hbm_bps(run.device_kind)
        * run.summary.devices)
