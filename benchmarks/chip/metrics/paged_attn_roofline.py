"""The paged decode attention kernel's share of its roofline over the
traced steps: the least time the chip needs for the attention the decoded
tokens require (live positions only), over the device time of the ops the
trace places inside `paged_decode_attention`. Nothing to read where the
cell's reference counts no attention work."""
import work

KERNEL = "paged_decode_attention"


def read(run):
    if not run.trace or not run.peaks:
        return None
    kernel_s = sum(v for k, v in run.trace["op_s"].items() if KERNEL in k)
    if kernel_s <= 0:
        return None
    ctxs = [c for s in run.traced_steps() for c in work.contexts(s.slots)]
    flops = [work.attn_flops(run.cell, c) for c in ctxs]
    nbytes = [work.attn_bytes(run.cell, c) for c in ctxs]
    if None in flops or None in nbytes:
        return None
    return 100.0 * work.least_time(sum(flops), sum(nbytes), run.peaks) / kernel_s
