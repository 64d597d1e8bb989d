"""XLA compiles inside the window (``jax.monitoring``'s backend-compile
event, loads from the persistent cache included) per campaign
completed: 0 when every program the window runs was built in set-up."""


def read(ctx):
    if ctx["kind"] != "sweep" or not ctx["jobs"]:
        return None
    return ctx["compiles"] / ctx["jobs"]
