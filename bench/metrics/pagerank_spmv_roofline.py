"""Local edge work: the least time an exact float32 PageRank step could
take at the chip's HBM bandwidth (``roofline.pagerank_iteration_bytes``,
the bytes of ``pagerank_edge_roofline``) over the device self time per
step under the program's ``localops.*`` scopes (``scopes.py``), in
percent."""

import roofline
import scopes


def read(run):
    if run.algo != "pagerank":
        return None
    reading = scopes.reading(run)
    spmv_s = reading.under("localops.") if reading else None
    if not spmv_s:
        return None
    steps = sum(rec["rounds"] for rec in run.launches)
    least_s = (roofline.pagerank_iteration_bytes(run.n, run.arcs) * steps
               / run.peaks["hbm_bytes_per_s"])
    return least_s / spmv_s * 100
