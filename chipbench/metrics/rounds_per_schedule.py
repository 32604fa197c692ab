"""Stacked sweep rounds per schedule: the sum of the schedules'
``solver_stats["stacked_rounds"]`` (core/rails.run_stacked_sweeps),
over the schedules of the window."""


def read(run):
    scheds = run.schedules
    if not scheds:
        return None
    return sum(s.solver_stats.get("stacked_rounds", 0)
               for s in scheds) / len(scheds)
