"""Local edge work: device busy time in the window, from the
profiler trace, per BFS round run in it."""


def read(run):
    if run.algo != "bfs" or run.trace is None:
        return None
    rounds = sum(rec["rounds"] for rec in run.launches)
    return run.trace.busy_s / rounds * 1e3
