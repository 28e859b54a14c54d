"""Host build: wall seconds of the program's own ``repro.graph.ell``
span (the four blocked-ELL builds of ``partition_graph``), on the
profiler trace's host clock."""

import scopes


def read(run):
    reading = scopes.reading(run)
    return reading.span_s("graph.ell") if reading else None
