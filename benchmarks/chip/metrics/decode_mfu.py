"""Model operations of the window's decode steps (the held matmul weights
and attention at each slot's live context, per decoded token) over their
host time times the chip's bf16 peak. Nothing to read where the cell's
reference counts no decode operations."""
import work


def read(run):
    steps = [s for s in run.steps if run.t_open <= s.t0 and s.t1 <= run.t_close]
    t = sum(s.t1 - s.t0 for s in steps)
    if not t or not run.peaks:
        return None
    per_token = [work.decode_flops(run.cell, c) for s in steps for c in work.contexts(s.slots)]
    if None in per_token:
        return None
    return 100.0 * sum(per_token) / (t * run.peaks["bf16_flops_per_s"])
