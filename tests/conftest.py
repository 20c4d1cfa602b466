import os

# The suite never switches the persistent compilation cache on: not in
# this process, and not in the benchmark drivers the tests start, which
# inherit the environment.  Set before jax is imported (jax reads it then).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 CPU device;
# only launch/dryrun.py forces 512 host devices (before any jax import),
# and the multi-device jax-shard cross-validation runs in a subprocess
# (tests/_shard_check.py) for the same reason: the device count is frozen
# at backend init.  The CI shard job opts the whole pytest process into 4
# devices via env XLA_FLAGS instead.


@pytest.fixture
def rng():
    return np.random.default_rng(0)
