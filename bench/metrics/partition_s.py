"""Host build: seconds in ``partition_graph`` (COO shards and the
blocked-ELL layout), on the host clock."""


def read(run):
    return run.partition_s
