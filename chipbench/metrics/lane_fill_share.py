"""Share of the (lane, column) cells computed by the DP and k-best
dispatches of the window that are real lanes and columns and not
padding, in percent: the jax backend's ``io_stats["lane_slots_used"]``
over ``io_stats["lane_slots"]``."""


def read(run):
    slots = run.counters.get("lane_slots", 0)
    if not slots:
        return None
    return 100.0 * run.counters["lane_slots_used"] / slots
