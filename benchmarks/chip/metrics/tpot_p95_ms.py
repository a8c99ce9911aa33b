"""95th percentile, over requests due in the window with two or more tokens
on the host by its close, of (last token time - first) / (tokens - 1)."""
import numpy as np


def read(run):
    vals = [(r.last - r.first) / (r.n_window - 1) for r in run.in_window()
            if r.n_window >= 2]
    return float(np.percentile(vals, 95)) * 1e3 if vals else None
