"""Reference implementations: the lax.scan cores of ``repro.core.sim_jax``.

Unlike the other kernel families (whose refs are standalone jnp oracles),
the msj_scan oracles *are* the production jax-batch scan cores — the whole
point of the kernel family is to be bit-identical (rtol=0) to them, and
they in turn are pinned event-for-event against the Python engine.  These
thin jitted wrappers expose them with the kernel call signatures;
``tests/test_sim_cross.py`` pins each kernel against its ref at the raw
event-stream level (on top of the end-to-end ``engine="pallas"``
cross-validation through the ``sim_batch`` wrappers).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sim_jax import _bs_core, _fcfs_core, _modbs_core


@partial(jax.jit, static_argnames=("k",))
def fcfs_scan_ref(arrival, need, service, *, k: int):
    """vmapped FCFS scan core: [R, J] arrays -> starts [R, J]."""
    return jax.vmap(lambda a, n, v: _fcfs_core(a, n, v, k))(
        arrival, need, service)


@partial(jax.jit, static_argnames=("s_max", "h"))
def modbs_scan_ref(arrival, cls, need, service, *, slots, s_max: int,
                   h: int):
    """vmapped ModBS scan core -> (blocked [R, J], starts [R, J])."""
    sl = jnp.asarray(slots, jnp.int32)
    return jax.vmap(
        lambda a, c, n, v: _modbs_core(a, c, n, v, sl, s_max, h))(
        arrival, cls, need, service)


@partial(jax.jit, static_argnames=("s_max", "h", "q_cap"))
def bs_scan_ref(arrival, cls, need, service, *, slots, s_max: int,
                h: int, q_cap: int):
    """Hand-vectorized BS-π event scan core -> (tagged, rec_t, ring peak)."""
    return _bs_core(arrival, cls, need, service,
                    jnp.asarray(slots, jnp.int32), s_max, h, q_cap)
