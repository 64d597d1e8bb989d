"""Traces of a sweep engine (``dse.TRACE_COUNTS``, both backends) inside
the window, per campaign completed: 0 when every shape was warmed up."""


def read(ctx):
    if ctx["kind"] != "sweep" or not ctx["jobs"]:
        return None
    return sum(ctx["trace_counts_delta"].values()) / ctx["jobs"]
