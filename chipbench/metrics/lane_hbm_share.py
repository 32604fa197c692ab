"""Share of the device's peak HBM bandwidth that the traced schedules'
lane dispatches use, in percent: the bytes they must move
(``solver_stats["lane_dispatches"]`` through chipbench.roofline) over
peak bandwidth × device busy time (chipbench.trace; the lane programs
are all of it).  A memory bound only; None where the program does not
count its lane dispatches."""

from chipbench import roofline


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    moved = roofline.schedules_bytes(run.traced_schedules)
    if moved is None:
        return None
    import jax

    peak = roofline.peak_hbm_bytes_per_s(jax.devices()[0].device_kind)
    return 100.0 * moved / (peak * run.trace["busy_s"])
