"""Seconds per whole PageRank solve: the window's time over the
number of solves completed in it."""


def read(run):
    if run.algo != "pagerank":
        return None
    return run.window_s / len(run.launches)
