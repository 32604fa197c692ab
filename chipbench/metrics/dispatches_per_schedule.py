"""Device kernel dispatches per schedule: the jax backend's
``io_stats["kernel_dispatches"]`` over the window, per schedule."""


def read(run):
    n = len(run.schedules)
    if not n or "kernel_dispatches" not in run.counters:
        return None
    return run.counters["kernel_dispatches"] / n
