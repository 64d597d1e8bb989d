"""Seconds a request waited in the sweep service's queue before a slot
took it, the mean over the requests admitted in the window
(``repro.obs.COUNTS``: ``service.queue_wait_s`` over
``service.admitted``)."""


def read(ctx):
    delta = ctx.get("obs_counts_delta")
    if ctx["kind"] != "served" or delta is None \
            or not delta["service.admitted"]:
        return None
    return delta["service.queue_wait_s"] / delta["service.admitted"]
