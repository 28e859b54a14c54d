"""Engine: seconds to lower and compile the program, or to load it
from the persistent compilation cache, on the host clock."""


def read(run):
    return run.compile_s
