"""Certified schedules completed in the window, over the window's
length (first call to the last request's return)."""


def read(run):
    return len(run.schedules) / run.window.seconds
