"""Median, over requests due in the window and admitted in it, of due time
to the start of the `try_admit` call that seated the request."""
import numpy as np


def read(run):
    vals = [r.admit_start - r.due for r in run.in_window()
            if r.admit_start is not None and r.admit_start <= run.t_close]
    return float(np.median(vals)) * 1e3 if vals else None
