"""Median, over the traced window's admissions that seated a request, of the
program's `serve.admit` span less the device work it waited on (its
``hicr.wait:*`` descendants): the host's own time per admission."""
import program_spans


def read(run):
    return program_spans.admit_host_ms_p50(run.spans)
