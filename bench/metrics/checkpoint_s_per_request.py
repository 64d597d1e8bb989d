"""Host seconds of the runner's checkpoint saves
(``repro.runner.checkpoint`` spans) per request completed in the traced
window (``benchlib.program_spans``)."""
from benchlib import program_spans


def read(ctx):
    return program_spans.per_job(ctx, "served", "repro.runner.checkpoint")
