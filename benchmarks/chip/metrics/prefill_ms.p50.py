"""Median, over the traced window's admissions that seated a request, of the
program's `serve.admit.prefill` span: the tail prefill from its dispatch to
its result on the host."""
import program_spans


def read(run):
    return program_spans.prefill_ms_p50(run.spans)
