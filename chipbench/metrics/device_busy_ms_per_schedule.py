"""Milliseconds in which an operation ran on the device, per schedule,
over the traced requests (chipbench.trace)."""


def read(run):
    n = len(run.traced_schedules)
    if run.trace is None or not n:
        return None
    return 1e3 * run.trace["busy_s"] / n
