"""Tests of the benchmark harness, on the CPU at tiny sizes.

They drive the harness's own functions (``bench/run.py``'s ``run_cell``
and the modules beside it) and never load a TPU library.  The persistent
compilation cache stays off, in this process and in any child, as in the
repository's own suite.
"""

import os
import sys

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture(scope="session")
def counter():
    import run
    return run.CompileCounter()
