"""Set-up time: process start to the window's start (generation,
host build, upload, compile or cache load, warm-up launch)."""


def read(run):
    return run.setup_s
