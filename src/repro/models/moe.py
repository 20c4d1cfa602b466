"""Mixture-of-Experts FFN: top-k router + capacity-based scatter dispatch.

Dispatch is *scatter/gather based* (``.at[].add`` into an ``[E, C, D]``
buffer), NOT the one-hot-einsum dispatch: the einsum form costs
``T·E·C·D`` MAC FLOPs — for deepseek-v3 that is ~50% of the expert-FFN
FLOPs, pure waste that would pollute the roofline compute term.  Scatter
costs bytes, which is what dispatch physically is.

Sharding: expert weights carry the 'expert' logical axis (physical:
'model' — the EP group IS the TP group).  Token buffers are sharded over
'batch'; the [E, C, D] dispatch buffer is shard-constrained over 'expert',
so XLA inserts the all-to-all at the dispatch/combine boundary.  Capacity
is per *router chunk* (a lax.scan over token chunks bounds the dispatch
buffer and the routing one-hots to O(chunk) regardless of sequence length).

Router: softmax over expert logits in float32, top-k, renormalized combine
weights (deepseek-v3 style), plus the standard load-balance auxiliary loss
(Shazeer/GShard form: E · Σ_e f_e · p_e).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .config import ArchConfig, MoECfg
from .layers import PDef


def moe_param_defs(cfg: ArchConfig) -> dict[str, Any]:
    """Per-layer MoE params (stacked over layers by the caller)."""
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    defs: dict[str, Any] = {
        "router": PDef((d, m.num_experts), (None, None), "scaled"),
        "w_gate": PDef((m.num_experts, d, f), ("expert", "fsdp", None), "scaled"),
        "w_up": PDef((m.num_experts, d, f), ("expert", "fsdp", None), "scaled"),
        "w_down": PDef((m.num_experts, f, d), ("expert", None, "fsdp"), "scaled"),
    }
    if m.num_shared:
        fs = f * m.num_shared
        defs["shared_gate"] = PDef((d, fs), ("fsdp", "tp"), "scaled")
        defs["shared_up"] = PDef((d, fs), ("fsdp", "tp"), "scaled")
        defs["shared_down"] = PDef((fs, d), ("tp", "fsdp"), "scaled")
    return defs


def _capacity(m: MoECfg, tokens: int) -> int:
    c = int(tokens * m.top_k / m.num_experts * m.capacity_factor)
    return max(m.top_k, (c + 3) // 4 * 4)  # pad to a multiple of 4


def route(x, router_w, m: MoECfg):
    """x: [T, D] -> (weights [T,k], experts [T,k] int32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, m.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balance loss:  E · Σ_e  f_e · p̄_e
    E = m.num_experts
    f_e = jnp.zeros(E, jnp.float32).at[experts.reshape(-1)].add(1.0)
    f_e = f_e / (x.shape[0] * m.top_k)
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e)
    return weights, experts.astype(jnp.int32), aux


def _dispatch_combine(xc, weights, experts, w_gate, w_up, w_down, m: MoECfg,
                      compute_dtype):
    """One chunk: xc [T, D] -> [T, D] through capacity-C expert buffers."""
    T, D = xc.shape
    E, k = m.num_experts, m.top_k
    C = _capacity(m, T)

    flat_e = experts.reshape(-1)                       # [T*k]
    # position of each (token, slot) within its expert's buffer:
    #   pos[j] = #{j' < j : e_j' == e_j}   via a cumsum over one-hot [T*k, E]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot          # exclusive
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    dropped = pos >= C
    pos = jnp.where(dropped, C, pos)                   # dump row C (padding)

    # scatter tokens -> [E, C+1, D] (row C collects drops, sliced off)
    src = jnp.repeat(xc, k, axis=0).astype(compute_dtype)   # [T*k, D]
    buf = jnp.zeros((E, C + 1, D), compute_dtype)
    buf = buf.at[flat_e, pos].add(src, mode="drop")
    buf = buf[:, :C]
    buf = _expert_constraint(buf)

    # expert FFN:  [E, C, D] x [E, D, F]
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(compute_dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   w_down.astype(compute_dtype))
    y = _expert_constraint(y)

    # gather back + weighted combine
    y = jnp.concatenate([y, jnp.zeros((E, 1, D), y.dtype)], axis=1)  # drop row
    out = y[flat_e, pos]                                # [T*k, D]
    w = jnp.where(dropped, 0.0, weights.reshape(-1)).astype(compute_dtype)
    out = (out * w[:, None]).reshape(T, k, D).sum(axis=1)
    return out


def _expert_constraint(x):
    """Shard the [E, C, D] buffer over the expert axis when inside a mesh."""
    from ..parallel.sharding import shard_constraint, DEFAULT_RULES
    return shard_constraint(x, DEFAULT_RULES, ("expert", None, None))


def moe_ffn(x, params, cfg: ArchConfig, *, chunk: int = 4096):
    """x: [B, S, D] -> ([B, S, D], aux_loss).

    Dispatch strategy (§Perf iteration A1): under a mesh with a >1 'model'
    axis, the shard_map all-to-all path is used — measured 91.7 TB -> ~0.2
    TB of wire on deepseek train_4k vs the pure-SPMD scatter, which XLA
    partitions by replicating the expert buffer.  Outside a mesh (CPU smoke
    tests) the scatter path runs; both paths share route/positions math
    and are cross-validated in tests.
    """
    mesh = _current_mesh()
    if mesh is not None and not mesh.empty and \
            "model" in mesh.axis_names and mesh.shape["model"] > 1:
        tokens = x.shape[0] * x.shape[1]
        from ..launch.mesh import data_shards
        per_dev = tokens // (data_shards(mesh) * mesh.shape["model"])
        if per_dev >= cfg.moe.num_experts // 4:    # enough tokens to slice
            return _moe_ffn_shard_map(x, params, cfg, mesh)
    return _moe_ffn_spmd(x, params, cfg, chunk=chunk)


def _moe_ffn_spmd(x, params, cfg: ArchConfig, *, chunk: int = 4096):
    """Pure-SPMD scatter path (single-device / smoke-test fallback)."""
    m = cfg.moe
    B, S, D = x.shape
    dt = jnp.dtype(cfg.compute_dtype)
    from .layers import _act
    xf = _act(x.reshape(B * S, D), ("batch", None))
    T = B * S
    chunk = min(chunk, T)
    if T % chunk:
        chunk = T  # fall back to a single chunk (small smoke shapes)
    n = T // chunk
    xs = xf.reshape(n, chunk, D)

    def body(aux, xc):
        w, e, a = route(xc, params["router"], m)
        y = _dispatch_combine(xc, w, e, params["w_gate"], params["w_up"],
                              params["w_down"], m, dt)
        return aux + a, y

    aux, ys = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    out = ys.reshape(B, S, D).astype(x.dtype)

    if m.num_shared:
        from .layers import swiglu
        out = out + swiglu(x, params["shared_gate"], params["shared_up"],
                           params["shared_down"])
    return out, aux / n


def _current_mesh():
    from ..parallel.sharding import _current_mesh as cm
    return cm()


def _moe_ffn_shard_map(x, params, cfg: ArchConfig, mesh):
    """Expert-parallel dispatch as explicit collectives (shard_map).

    Per device (data shards x model shards): tokens are batch-sharded and
    replicated over 'model'; each model rank takes its 1/|model| slice, so
    dispatch capacity math is device-local.  Then:

        local scatter   -> buf [E, C_loc, D]             (no comms)
        all_to_all      -> [E_loc, model*C_loc, D]       (token payload)
        expert FFN      -> same shape                    (local matmuls,
                           fsdp dim of the weights all-gathered in bf16)
        all_to_all back -> [E, C_loc, D]
        local combine   -> y slice;  all_gather over 'model' restores the
                           batch-sharded/model-replicated activation layout

    Wire per device ~= 2 x a2a payload + y gather + bf16 weight gathers —
    the information-theoretic cost of EP, vs XLA's replicate-the-buffer
    lowering of the scatter.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, D = x.shape
    dt = jnp.dtype(cfg.compute_dtype)
    E = m.num_experts
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model_size = mesh.shape["model"]
    E_loc = E // model_size
    assert E % model_size == 0

    def body(xl, router, wg, wu, wd):
        # xl: [T_ds, D] (this data shard's tokens, replicated over model)
        T_ds = xl.shape[0]
        T_loc = T_ds // model_size
        r = jax.lax.axis_index("model")
        xs = jax.lax.dynamic_slice_in_dim(xl, r * T_loc, T_loc, 0)

        weights, experts, aux = route(xs, router, m)
        flat_e = experts.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        C = _capacity(m, T_loc)
        dropped = pos >= C
        pos = jnp.where(dropped, C, pos)

        src = jnp.repeat(xs, m.top_k, axis=0).astype(dt)
        buf = jnp.zeros((E, C + 1, D), dt)
        buf = buf.at[flat_e, pos].add(src, mode="drop")[:, :C]

        # a2a: every rank keeps its E_loc experts, receives peers' tokens
        buf = buf.reshape(model_size, E_loc, C, D)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                                 tiled=False)
        buf = buf.transpose(1, 0, 2, 3).reshape(E_loc, model_size * C, D)

        # fsdp-dim gather of this rank's expert weights in bf16.  The
        # optimization_barrier pins the cast BEFORE the gather — without it
        # XLA commutes the convert past the all-gather and moves f32 bits
        # (§Perf A3: measured 2x all-gather wire).
        def gathered(w, axis):
            wl = jax.lax.optimization_barrier(w.astype(dt))
            return jax.lax.all_gather(wl, data_axes, axis=axis,
                                      tiled=True) if data_axes else wl

        g = jnp.einsum("ecd,edf->ecf", buf, gathered(wg, 1))
        u = jnp.einsum("ecd,edf->ecf", buf, gathered(wu, 1))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, gathered(wd, 2))

        # inverse a2a back to the owning token shard
        y = y.reshape(E_loc, model_size, C, D).transpose(1, 0, 2, 3)
        y = jax.lax.all_to_all(y, "model", split_axis=0, concat_axis=0,
                               tiled=False)
        y = y.reshape(E, C, D)
        y = jnp.concatenate([y, jnp.zeros((E, 1, D), y.dtype)], axis=1)
        out = y[flat_e, pos]
        wgt = jnp.where(dropped, 0.0, weights.reshape(-1)).astype(dt)
        out = (out * wgt[:, None]).reshape(T_loc, m.top_k, D).sum(axis=1)

        # restore the model-replicated layout
        out = jax.lax.all_gather(out, "model", axis=0, tiled=True)
        aux = jax.lax.pmean(aux, "model")
        return out, aux

    xf = x.reshape(B * S, D)
    batch_spec = P(data_axes if data_axes else None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec, P(None, None),
                  P("model", data_axes, None), P("model", data_axes, None),
                  P("model", None, data_axes)),
        out_specs=(batch_spec, P()),
        check_vma=False)
    out, aux = fn(xf, params["router"], params["w_gate"], params["w_up"],
                  params["w_down"])
    aux = jnp.mean(aux)
    out = out.reshape(B, S, D).astype(x.dtype)
    if m.num_shared:
        from .layers import swiglu
        out = out + swiglu(x, params["shared_gate"], params["shared_up"],
                           params["shared_down"])
    return out, aux


def moe_active_params_per_layer(cfg: ArchConfig) -> int:
    """Per-token active expert params in one MoE layer (router + top-k + shared)."""
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    active = d * m.num_experts                       # router
    active += m.top_k * 3 * d * f                    # routed experts
    active += m.num_shared * 3 * d * f               # shared experts
    return active
