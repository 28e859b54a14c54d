"""Superstep loop: iterations per solve, as each launch reports
them, averaged over the window's solves."""


def read(run):
    if run.algo != "pagerank":
        return None
    return sum(rec["rounds"] for rec in run.launches) / len(run.launches)
