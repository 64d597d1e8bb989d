"""Device seconds of the sweep engine's kernel per campaign completed in
the traced window; on several chips, the slowest chip's.  Which
operations are the kernel is the trace reduction's rule
(``trace_reduce.is_kernel``); a window in which none ran reads nothing,
and the run then fails."""


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != "sweep" or not tr or not ctx["jobs"]:
        return None
    worst = max(d["kernel_s"] for d in tr["devices"])
    return worst / ctx["jobs"] if worst > 0 else None
