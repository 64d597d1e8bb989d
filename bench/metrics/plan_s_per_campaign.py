"""Host seconds of ``dse.plan_grid`` (the program's ``repro.dse.plan``
spans: stack the hardware configurations and broadcast the grid), per
campaign completed in the traced window (``benchlib.program_spans``)."""
from benchlib import program_spans


def read(ctx):
    return program_spans.per_job(ctx, "sweep", "repro.dse.plan")
