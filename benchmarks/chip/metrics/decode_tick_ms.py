"""Host time of the window's `step()` calls per decode tick they ran."""


def read(run):
    steps = [s for s in run.steps if run.t_open <= s.t0 and s.t1 <= run.t_close]
    ticks = sum(s.ticks for s in steps)
    return sum(s.t1 - s.t0 for s in steps) / ticks * 1e3 if ticks else None
