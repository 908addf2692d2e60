import os

# Tests run on the single real CPU device; the dry-run (and only the
# dry-run) forces 512 placeholder devices in its own process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

from repro.launch import compile_cache  # noqa: E402

jax.config.update("jax_enable_x64", False)

# Every Experiment.run() traces and compiles its programs anew, so the
# suite compiles the same small programs over and over.  The entry points'
# persistent compile cache, with no compile-time floor, lets a repeat
# load the first compile instead.
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
