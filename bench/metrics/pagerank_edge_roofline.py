"""Local edge work: the least time an exact float32 PageRank step could
take at the chip's HBM bandwidth (``roofline.pagerank_iteration_bytes``)
over the device busy time per step in the window, in percent."""

import roofline


def read(run):
    if run.algo != "pagerank" or run.trace is None or not run.trace.busy_s:
        return None
    steps = sum(rec["rounds"] for rec in run.launches)
    least_s = (roofline.pagerank_iteration_bytes(run.n, run.arcs) * steps
               / run.peaks["hbm_bytes_per_s"])
    return least_s / run.trace.busy_s * 100
