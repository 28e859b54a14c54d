"""Peak device memory in use over the run, in GB (10**9 bytes).

The source is the device allocator's own counter, ``peak_bytes_in_use``
of ``Device.memory_stats()``, read by the harness after the window on
the fullest device; it is no profiler trace.  The counter covers the
whole process, so it includes the on-device generation and relabelling
of the graph before the program ran; the harness prints the counter as
it stands after generation, which has to stay below the program's
peak for this metric to be the program's."""


def read(run):
    if not run.peak_bytes:
        return None
    return run.peak_bytes / 1e9
