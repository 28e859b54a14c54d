"""Superstep loop: rounds per search, as each launch reports them,
averaged over the window's searches."""


def read(run):
    if run.algo != "bfs":
        return None
    return sum(rec["rounds"] for rec in run.launches) / len(run.launches)
