import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real jitted entry point (train_step /
prefill / decode_step) with production in_shardings, lowers it against
ShapeDtypeStruct stand-ins (nothing is allocated), compiles it, and
records memory_analysis / cost_analysis / the parsed collective schedule.

    PYTHONPATH=src python -m repro.launch.dryrun --mesh both --out dryrun.json
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi_9b --shape train_4k
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import data_shards, make_production_mesh
from repro.launch.roofline import Roofline, model_flops
from repro.models.model import (Model, cache_axes, cache_specs,
                                decode_inputs, prefill_inputs, train_inputs)
from repro.optim.optimizer import AdamWConfig, opt_state_axes
from repro.parallel.sharding import DEFAULT_RULES, tree_shardings_sized
from repro.train.step import make_train_step, train_state_specs


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    kind: str        # "train" | "prefill" | "decode"
    seq: int
    batch: int


SHAPES = {
    "train_4k": ShapeCell("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeCell("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeCell("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeCell("long_500k", "decode", 524288, 1),
}

# grad-accumulation microbatch count per arch (train_4k); clamped so the
# per-microbatch batch still divides the data shards of the mesh.
MICROBATCHES = {
    "llama_3_2_vision_90b": 16,
    "starcoder2_7b": 8,
    "stablelm_3b": 4,
    "internlm2_20b": 8,
    "yi_9b": 8,
    "moonshot_v1_16b_a3b": 8,
    "deepseek_v3_671b": 4,   # §Perf A2+A4: 16->8->4 quarters per-step FSDP gathers
    "jamba_1_5_large_398b": 16,
    "seamless_m4t_large_v2": 4,
    "rwkv6_7b": 8,
}


def applicable(arch: str, shape: ShapeCell) -> bool:
    """long_500k needs sub-quadratic attention (DESIGN §Arch-applicability)."""
    if shape.name != "long_500k":
        return True
    return get_config(arch).supports_long_context


def _shardings(axes_tree, spec_tree, mesh):
    return tree_shardings_sized(axes_tree, spec_tree, DEFAULT_RULES, mesh)


def lower_cell(arch: str, shape: ShapeCell, mesh, rules=DEFAULT_RULES):
    """Build + lower one cell.  Returns (lowered, specs_meta)."""
    cfg = get_config(arch)
    model = Model(cfg)
    if shape.kind == "train":
        M = min(MICROBATCHES.get(arch, 8), shape.batch // data_shards(mesh))
        M = max(M, 1)
        step = make_train_step(cfg, AdamWConfig(), microbatches=M)
        p, opt, batch = train_state_specs(cfg, shape.batch, shape.seq)
        pa = model.param_axes()
        in_sh = (
            _shardings(pa, p, mesh),
            _shardings(opt_state_axes(pa), opt, mesh),
            _shardings(train_inputs(cfg, shape.batch, shape.seq, "axes"),
                       batch, mesh),
        )
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=in_sh).lower(p, opt, batch)
        return lowered, {"microbatches": M}
    if shape.kind == "prefill":
        batch = prefill_inputs(cfg, shape.batch, shape.seq, "spec")
        p = model.param_specs()
        in_sh = (
            _shardings(model.param_axes(), p, mesh),
            _shardings(prefill_inputs(cfg, shape.batch, shape.seq, "axes"),
                       batch, mesh),
        )
        fn = lambda params, b: model.prefill(params, b)  # noqa: E731
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh).lower(p, batch)
        return lowered, {}
    # decode — SERVING rules (§Perf C1): ZeRO-3 fsdp sharding is a training
    # layout; at decode it forces per-step weight/activation collectives.
    # Serving keeps weights TP/EP-sharded over 'model' only (llama-90B:
    # 11 GB/chip bf16) and spends the data axis purely on request batch.
    serve_rules = rules.replace(fsdp=None)
    # §Perf C2: serving weights live in bf16 (the serving checkpoint),
    # not the fp32 training master copy — halves weight reads per step
    # and removes the per-layer cast traffic.
    p = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        model.param_specs())
    caches = cache_specs(cfg, shape.batch, shape.seq)
    tok = jax.ShapeDtypeStruct((shape.batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    in_sh = (
        tree_shardings_sized(model.param_axes(), p, serve_rules, mesh),
        tree_shardings_sized(cache_axes(cfg), caches, serve_rules, mesh),
        tree_shardings_sized(("batch", None), tok, serve_rules, mesh),
        None,
    )
    fn = lambda params, c, t, i: model.decode_step(params, c, t, i)  # noqa
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh).lower(p, caches, tok, pos)
    return lowered, {}


def run_cell(arch: str, shape: ShapeCell, mesh, mesh_name: str,
             skip_compile: bool = False) -> dict[str, Any]:
    t0 = time.time()
    rec: dict[str, Any] = {"arch": arch, "shape": shape.name,
                           "mesh": mesh_name}
    cfg = get_config(arch)
    try:
        lowered, meta = lower_cell(arch, shape, mesh)
        rec.update(meta)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        try:
            mem = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)
            }
        except Exception as e:  # pragma: no cover
            rec["memory"] = {"error": str(e)}

        ndev = mesh.devices.size
        # trip-count-aware walker (XLA's cost_analysis counts scan bodies
        # once — useless for scanned models; see launch.hlo_analysis)
        analysis = analyze_hlo(compiled.as_text(), ndev)
        mf = model_flops(cfg, shape.kind, shape.batch, shape.seq) / ndev
        roof = Roofline(flops=analysis.flops,
                        bytes_accessed=analysis.bytes_accessed,
                        wire_bytes=analysis.wire_bytes,
                        model_flops_per_device=mf)
        rec["cost"] = {"flops": analysis.flops,
                       "bytes_accessed": analysis.bytes_accessed,
                       "bytes_unadjusted": analysis.bytes_unadjusted,
                       "kernel_bytes": analysis.kernel_bytes,
                       "unresolved_loops": analysis.unresolved_loops}
        rec["collectives"] = {
            "total_wire_bytes": analysis.wire_bytes,
            "count": analysis.coll_count,
            "by_type": {k: dict(v) for k, v in
                        analysis.coll_by_type.items()}}
        rec["model_flops_per_device"] = mf
        rec["roofline"] = roof.row()
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES.values()) if args.shape == "all" else \
        [SHAPES[args.shape]]
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))

    records = []
    for mesh_name, mesh in meshes:
        for arch in archs:
            for shape in shapes:
                if not applicable(arch, shape):
                    records.append({"arch": arch, "shape": shape.name,
                                    "mesh": mesh_name, "ok": True,
                                    "skipped": "full-attention arch; "
                                    "long_500k needs sub-quadratic"})
                    continue
                rec = run_cell(arch, shape, mesh, mesh_name)
                r = rec.get("roofline", {})
                status = "OK " if rec["ok"] else "FAIL"
                print(f"[{status}] {mesh_name:18s} {arch:24s} "
                      f"{shape.name:12s} "
                      f"comp={r.get('compute_s', 0):.4f}s "
                      f"mem={r.get('memory_s', 0):.4f}s "
                      f"coll={r.get('collective_s', 0):.4f}s "
                      f"dom={r.get('dominant', '-'):10s} "
                      f"({rec.get('total_s')}s)"
                      + ("" if rec["ok"] else
                         f"  {rec.get('error', '')[:160]}"),
                      flush=True)
                records.append(rec)

    n_fail = sum(1 for r in records if not r.get("ok"))
    print(f"\n{len(records)} cells, {n_fail} failures")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1, default=float)
        print(f"wrote {args.out}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
