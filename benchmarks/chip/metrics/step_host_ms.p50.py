"""Median, over the traced window's `serve.step` spans, of the step less its
wait for the decode interval: the host's own time per interval."""
import program_spans


def read(run):
    return program_spans.step_host_ms_p50(run.spans)
