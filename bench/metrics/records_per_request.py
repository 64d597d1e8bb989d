"""Stream records the client folded per request (``ClientStats``):
one per work unit of the service's runner."""


def read(ctx):
    if ctx["kind"] != "served" or not ctx["records"]:
        return None
    return sum(ctx["records"]) / len(ctx["records"])
