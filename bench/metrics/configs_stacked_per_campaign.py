"""Hardware configurations ``hwconfig.stack_configs`` stacked into device
arrays (``repro.obs.COUNTS["hwconfig.configs_stacked"]`` over the
window) per campaign completed: H for each ``plan_grid`` call."""


def read(ctx):
    delta = ctx.get("obs_counts_delta")
    if ctx["kind"] != "sweep" or delta is None or not ctx["jobs"]:
        return None
    return delta["hwconfig.configs_stacked"] / ctx["jobs"]
