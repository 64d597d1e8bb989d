"""Host seconds of the runner's work units (``repro.runner.unit`` spans)
less their wait for the device (``repro.runner.wait``), per request
completed in the traced window (``benchlib.program_spans``)."""
from benchlib import program_spans


def read(ctx):
    return program_spans.per_job(ctx, "served", "repro.runner.unit",
                                 less="repro.runner.wait")
