"""Median host time of a `try_admit` call that seated a request in the
window: the radix match, page reservation, tail prefill and commit, each
ending in a host transfer."""
import numpy as np


def read(run):
    vals = [b - a for a, b in run.admits if run.t_open <= a and b <= run.t_close]
    return float(np.median(vals)) * 1e3 if vals else None
