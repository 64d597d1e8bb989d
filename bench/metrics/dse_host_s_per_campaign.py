"""Host seconds of the outermost ``dse.sweep`` call (the program's
``repro.dse.sweep`` spans) less the part in which it waited for the
device (``repro.dse.wait``), per campaign completed in the traced window
(``benchlib.program_spans``)."""
from benchlib import program_spans


def read(ctx):
    return program_spans.per_job(ctx, "sweep", "repro.dse.sweep",
                                 less="repro.dse.wait")
