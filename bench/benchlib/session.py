"""What every process of the benchmark does before it touches the chip."""
from __future__ import annotations

import os
import sys
import tempfile


def prepare(chips: int):
    """Point the autotune cache at a fresh, empty file (no winner timed
    elsewhere, or on a CPU, picks what is measured), check that JAX sees
    ``chips`` TPU chips, and turn the persistent compilation cache on at
    its fixed path.  Returns the autotune directory to remove at exit,
    or None, having printed why, when the chips are not there."""
    tune = tempfile.mkdtemp(prefix="bench-autotune-")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(tune, "autotune.json")
    os.environ.pop("REPRO_AUTOTUNE", None)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        os.rmdir(tune)
        return None
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # the per-bucket compiles are small: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return tune
