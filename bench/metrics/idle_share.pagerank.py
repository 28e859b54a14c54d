"""Device: the share of the window in which no operation ran on the
device, from the profiler trace, for pagerank cells."""


def read(run):
    if run.algo != "pagerank" or run.trace is None:
        return None
    return (1 - run.trace.busy_s / run.trace.window_s) * 100
