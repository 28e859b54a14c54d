"""Graph500 TEPS: the input edges within every searched component,
over the window's time (first launch's start to last launch's end)."""


def read(run):
    if run.algo != "bfs":
        return None
    return sum(rec["work"] for rec in run.launches) / run.window_s
