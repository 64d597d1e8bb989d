"""Host seconds of the sweep service's admission (``repro.service.admit``
spans: merge the requests' grids and stack their configurations, build
the runner, attach checkpoints) per request completed in the traced
window (``benchlib.program_spans``)."""
from benchlib import program_spans


def read(ctx):
    return program_spans.per_job(ctx, "served", "repro.service.admit")
