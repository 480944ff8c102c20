"""Seconds from the start of the process to the opening of the window:
data, index build, engine warm-up and every compile."""


def read(run):
    return run.setup_s
