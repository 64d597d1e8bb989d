"""Share of the traced window in which no operation ran on the device,
in a sweep cell (the mean over the devices of a mesh)."""


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != "sweep" or not tr or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
