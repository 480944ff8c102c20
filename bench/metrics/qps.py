"""Queries completed inside the window, per second of the window."""


def read(run):
    if run.loop != "closed":
        return None
    return run.completed_in_window / run.window_s
