"""Set-up time: process start to the window's first unit of work, in s."""


def read(run):
    return run.setup_s
