"""The paged decode attention kernel's share of its roofline over the
traced steps: the least time the chip needs for the attention the decoded
tokens require (live positions only), over the device time of the ops the
trace places inside `paged_decode_attention`."""
import work

KERNEL = "paged_decode_attention"


def read(run):
    if not run.trace or not run.peaks:
        return None
    kernel_s = sum(v for k, v in run.trace["op_s"].items() if KERNEL in k)
    if kernel_s <= 0:
        return None
    p = run.cell.program
    ctxs = [c for s in run.traced_steps() for c in work.contexts(s.slots)]
    flops = sum(work.attn_flops(p, c) for c in ctxs)
    nbytes = sum(work.attn_bytes(p, c) for c in ctxs)
    return 100.0 * work.least_time(flops, nbytes, run.peaks) / kernel_s
