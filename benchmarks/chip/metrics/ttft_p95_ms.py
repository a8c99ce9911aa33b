"""95th percentile, over every request due in the window, of due time to
first token on the host; a request still without one counts its wait until
the window closed."""
import numpy as np


def read(run):
    waits = [((r.first if r.first is not None and r.first <= run.t_close else run.t_close)
              - r.due) for r in run.in_window()]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
