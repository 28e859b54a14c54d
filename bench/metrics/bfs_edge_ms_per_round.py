"""Local edge work: device self time under the program's ``localops.*``
scopes in the window, from the profiler trace joined with the compiled
program's scope map (``scopes.py``), per BFS round run in it."""

import scopes


def read(run):
    if run.algo != "bfs":
        return None
    reading = scopes.reading(run)
    edge_s = reading.under("localops.") if reading else None
    if edge_s is None:
        return None
    rounds = sum(rec["rounds"] for rec in run.launches)
    return edge_s / rounds * 1e3
