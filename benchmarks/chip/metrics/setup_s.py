"""Set-up: process start to the window's start, compiles included."""


def read(run):
    return run.setup_s
