import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede every other import (jax locks the device count at first
# init); smoke tests / benches import repro without this module and see 1.

DOC = """Multi-pod dry-run (assignment deliverable (e)).

For every (architecture x input-shape) cell and both production meshes
(single-pod 16x16=256 chips, multi-pod 2x16x16=512 chips), lower + compile
the cell's step function against ShapeDtypeStruct stand-ins (no allocation),
then record:
  * memory_analysis()        (fits-per-device proof)
  * cost_analysis()          (flops / bytes for §Roofline)
  * collective bytes         (parsed from the optimized HLO; analysis/hlo.py)
  * the three roofline terms (core/roofline.TpuRooflineTerms)

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
Results land in results/dryrun/<arch>__<shape>__<mesh>.json.
"""


import argparse
import json
import math
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_bytes, remat_duplication
from repro.configs import SHAPES, ArchConfig, ShapeSpec, cells, get_config
from repro.core.roofline import TpuRooflineTerms
from repro.distributed.sharding import INFERENCE_RULES, resolve_spec
from repro.launch.mesh import make_production_mesh
from repro.models import params as pr
from repro.models.registry import build_model, input_specs
from repro.serving.serve_step import make_decode_step
from repro.train.optim import AdamWState, OptConfig
from repro.train.train_step import make_loss_fn, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

BATCH_LOGICAL = {
    "tokens": ("batch", None),
    "labels": ("batch", None),
    "frames": ("batch", None, None),
    "patches": ("batch", None, None),
    "positions": (None, "batch", None),
}


def _shard(mesh, shape, logical, rules=None):
    return NamedSharding(mesh, resolve_spec(tuple(shape), logical, mesh,
                                            rules))


def batch_shardings(mesh, structs: dict) -> dict:
    return {k: _shard(mesh, v.shape, BATCH_LOGICAL[k])
            for k, v in structs.items()}


def cache_logical_for(name: str, ndim: int, stacked: bool) -> tuple:
    lead = ("layers",) if stacked else ()
    n = name.split(".")[-1].strip("'] ").lower()
    base_nd = ndim - len(lead)
    if n in ("k", "v") and base_nd == 4:          # KV cache (B, KV, C, hd)
        return lead + ("batch", "kv_heads", "cache_seq", None)
    if n in ("cross_k", "cross_v"):               # (L, B, T, KV, hd)
        return ("layers", "batch", "cache_seq", "kv_heads", None)
    if n == "pos":
        return ("layers",) * ndim          # scalar, or (L,) when stacked
    if n == "h" and base_nd == 2:                 # RG-LRU state (B, W)
        return lead + ("batch", "mlp")
    if n == "conv" and base_nd == 3:              # (B, K-1, W)
        return lead + ("batch", None, "mlp")
    if n == "s" and base_nd == 4:                 # RWKV state (B, H, n, n)
        return lead + ("batch", "heads", None, None)
    if n in ("shift_tm", "shift_cm") and base_nd == 2:
        return lead + ("batch", None)
    return (None,) * ndim


def cache_shardings(mesh, cache_structs,
                    stacked_names=("scan", "self")) -> Any:
    named, treedef = jax.tree_util.tree_flatten_with_path(cache_structs)
    out = []
    for path, leaf in named:
        pstr = jax.tree_util.keystr(path)
        stacked = any(f"'{s}'" in pstr for s in stacked_names)
        logical = cache_logical_for(pstr, leaf.ndim, stacked)
        out.append(_shard(mesh, leaf.shape, logical))
    return jax.tree_util.tree_unflatten(treedef, out)


def param_bytes_per_device(structs, shardings, mesh) -> int:
    total = 0
    for sd, sh in zip(jax.tree.leaves(structs), jax.tree.leaves(shardings)):
        spec = sh.spec
        n = 1
        for i, dim in enumerate(sd.shape):
            axes = spec[i] if i < len(spec) else None
            div = 1
            if axes:
                axes = (axes,) if isinstance(axes, str) else axes
                div = math.prod(mesh.shape[a] for a in axes)
            n *= dim // div
        total += n * sd.dtype.itemsize
    return total


def _clone_cfg(cfg: ArchConfig, periods: int) -> ArchConfig:
    """Depth-reduced clone for the scan-cost extrapolation (§scan-correction):
    ``periods`` full pattern periods; lowered force-unrolled."""
    import dataclasses
    p = len(cfg.block_pattern)
    if cfg.family == "audio":
        return dataclasses.replace(cfg, num_layers=periods,
                                   encoder_layers=periods)
    return dataclasses.replace(cfg, num_layers=p * periods)


def _lower_and_compile(cfg: ArchConfig, shape: ShapeSpec, mesh, chips,
                       remat: str, force_unroll: bool,
                       infer_layout: bool = False):
    """Shared lowering path; returns (compiled, lower_s, compile_s,
    model_flops, specs, p_structs, p_shard).

    Lowering runs inside ``jax.sharding.set_mesh(mesh)`` so the models'
    activation sharding constraints (distributed.sharding.constrain) resolve
    against the production mesh."""
    with jax.sharding.set_mesh(mesh):
        return _lower_and_compile_inner(cfg, shape, mesh, chips, remat,
                                        force_unroll, infer_layout)


def _lower_and_compile_inner(cfg, shape, mesh, chips, remat, force_unroll,
                             infer_layout=False):
    model = build_model(cfg)
    model.force_unroll = force_unroll
    specs = model.specs()
    rules = INFERENCE_RULES if infer_layout else None
    p_structs = pr.shape_tree(specs, cfg.param_dtype)
    p_logical = pr.logical_tree(specs)
    p_shard = jax.tree.map(
        lambda sd, lg: _shard(mesh, sd.shape, lg, rules), p_structs,
        p_logical)
    in_structs = input_specs(cfg, shape)
    b_shard = batch_shardings(mesh, in_structs)

    t0 = time.time()
    if shape.kind == "train":
        opt_structs = AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            m=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                           p_structs),
            v=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                           p_structs),
            ef=None)
        opt_shard = AdamWState(step=NamedSharding(mesh, P()),
                               m=p_shard, v=p_shard, ef=None)
        fn = make_train_step(model, cfg, OptConfig(), remat=remat)
        jf = jax.jit(fn, in_shardings=(p_shard, opt_shard, b_shard))
        lowered = jf.lower(p_structs, opt_structs, in_structs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * cfg.params_billion_estimate() * 1e9 * tokens
    elif shape.kind == "prefill":
        loss_free = make_loss_fn  # unused; prefill = forward logits

        def prefill(params, batch):
            if cfg.family == "audio":
                return model.forward(params, batch["tokens"],
                                     batch["frames"])[0]
            return model.forward(params, batch["tokens"],
                                 positions=batch.get("positions"),
                                 patches=batch.get("patches"))[0]

        jf = jax.jit(prefill, in_shardings=(p_shard, b_shard))
        lowered = jf.lower(p_structs, in_structs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * cfg.params_billion_estimate() * 1e9 * tokens
    else:  # decode
        cache_structs = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        c_shard = cache_shardings(mesh, cache_structs)
        step_fn = make_decode_step(model, cfg)
        tok_struct = in_structs["tokens"]
        step_struct = jax.ShapeDtypeStruct((), jnp.int32)
        jf = jax.jit(step_fn, in_shardings=(
            p_shard, c_shard, _shard(mesh, tok_struct.shape, ("batch", None)),
            NamedSharding(mesh, P())))
        lowered = jf.lower(p_structs, cache_structs, tok_struct, step_struct)
        tokens = shape.global_batch
        model_flops = 2 * cfg.params_billion_estimate() * 1e9 * tokens
    lower_s = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    return compiled, lower_s, compile_s, model_flops, specs, p_structs, p_shard


def _analyze(compiled, chips) -> dict:
    try:
        mem = compiled.memory_analysis()
        mem_d = {k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "peak_memory_in_bytes",
            "generated_code_size_in_bytes") if hasattr(mem, k)}
    except Exception as e:                       # CPU backend may lack it
        mem_d = {"unavailable": str(e)}
    try:
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        flops_dev = float(cost.get("flops", 0.0))
        bytes_dev = float(cost.get("bytes accessed", 0.0))
    except Exception:
        flops_dev, bytes_dev = 0.0, 0.0
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {"mem": mem_d, "flops_dev": flops_dev, "bytes_dev": bytes_dev,
            "coll": coll, "dup": remat_duplication(hlo),
            "hlo_lines": hlo.count("\n")}


def _wkv_analytic_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """RWKV's WKV recurrence is a time-scan (cost-counted once); add the
    analytic (S-1)-step remainder: ~7*n^2 flops /step /head /batch /layer,
    x3 for the train backward."""
    if "rwkv" not in cfg.block_pattern or shape.kind == "decode":
        return 0.0
    n = cfg.resolved_head_dim
    steps = shape.seq_len - 1
    mult = 3.0 if shape.kind == "train" else 1.0
    return (cfg.num_layers * shape.global_batch * steps * cfg.num_heads *
            7 * n * n * mult)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             remat: str = "dots", extra_tag: str = "",
             correction: bool = True, infer_layout: bool = False,
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = math.prod(mesh.shape.values())

    compiled, lower_s, compile_s, model_flops, specs, p_structs, p_shard = \
        _lower_and_compile(cfg, shape, mesh, chips, remat, False,
                           infer_layout)
    a = _analyze(compiled, chips)

    # ---- scan-cost correction (two-point extrapolation over clone depth) ---
    model = build_model(cfg)
    del specs  # keep the full-model spec tree only via p_structs below
    specs = model.specs()
    n_scan = getattr(model, "n_full", 0)
    if cfg.family == "audio":
        n_scan = cfg.num_layers          # enc+dec scans, equal depths
    corr = {"applied": False}
    if correction and n_scan > 1:
        c1 = _analyze(_lower_and_compile(
            _clone_cfg(cfg, 1), shape, mesh, chips, remat, True,
            infer_layout)[0], chips)
        c2 = _analyze(_lower_and_compile(
            _clone_cfg(cfg, 2), shape, mesh, chips, remat, True,
            infer_layout)[0], chips)
        body_flops = max(0.0, c2["flops_dev"] - c1["flops_dev"])
        body_bytes = max(0.0, c2["bytes_dev"] - c1["bytes_dev"])
        body_coll = max(0, c2["coll"]["total_bytes"] - c1["coll"]["total_bytes"])
        corr = {"applied": True, "n_scan": n_scan,
                "body_flops_dev": body_flops, "body_bytes_dev": body_bytes,
                "body_collective_dev": body_coll}
        a["flops_dev"] += (n_scan - 1) * body_flops
        a["bytes_dev"] += (n_scan - 1) * body_bytes
        a["coll"]["total_bytes"] += (n_scan - 1) * body_coll

    wkv_extra = _wkv_analytic_flops(cfg, shape)   # global flops
    flops_global = a["flops_dev"] * chips + wkv_extra

    terms = TpuRooflineTerms(
        flops=flops_global, hbm_bytes=a["bytes_dev"] * chips,
        collective_bytes=a["coll"]["total_bytes"] * chips, chips=chips)
    pbytes = param_bytes_per_device(p_structs, p_shard, mesh)

    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": extra_tag,
        "kind": shape.kind, "chips": chips, "ok": True,
        "lower_s": round(lower_s, 2), "compile_s": round(compile_s, 2),
        "flops_per_device": a["flops_dev"], "bytes_per_device": a["bytes_dev"],
        "collective_bytes_per_device": a["coll"]["total_bytes"],
        "collective_by_op": a["coll"]["by_op"],
        "collective_counts": a["coll"]["counts"],
        "remat_duplication": round(a["dup"], 3),
        "memory_analysis": a["mem"],
        "scan_correction": corr,
        "wkv_analytic_flops": wkv_extra,
        "param_count": pr.param_count(specs),
        "param_bytes_per_device": pbytes,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / flops_global
                               if flops_global else None),
        "roofline": terms.as_dict(),
        "hlo_lines": a["hlo_lines"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--tag", default="")
    ap.add_argument("--infer-layout", action="store_true",
                    help="serving param layout: TP-resident, no FSDP gathers")
    ap.add_argument("--cfg-override", action="append", default=[],
                    help="e.g. --cfg-override num_heads=16")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    todo = cells() if args.all else [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for arch, shape in todo:
        for mk in meshes:
            tag = f"__{args.tag}" if args.tag else ""
            path = os.path.join(args.out, f"{arch}__{shape}__{mk}{tag}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"skip {path}")
                continue
            print(f"=== {arch} x {shape} x {mk} ===", flush=True)
            try:
                ov = {}
                for o in args.cfg_override:
                    k, v = o.split("=", 1)
                    ov[k] = int(v) if v.lstrip("-").isdigit() else v
                rec = run_cell(arch, shape, mk, remat=args.remat,
                               extra_tag=args.tag,
                               infer_layout=args.infer_layout,
                               overrides=ov or None)
            except Exception as e:
                rec = {"arch": arch, "shape": shape, "mesh": mk, "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            status = "OK" if rec.get("ok") else "FAIL " + rec.get("error", "")
            print(f"    -> {status} "
                  f"(lower {rec.get('lower_s', '?')}s, "
                  f"compile {rec.get('compile_s', '?')}s)", flush=True)


if __name__ == "__main__":
    main()
