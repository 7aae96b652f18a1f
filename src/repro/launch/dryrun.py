import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below may import jax.

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) cell against the production mesh with ShapeDtypeStruct stand-ins (no
allocation), print memory_analysis / cost_analysis, and emit the roofline
terms (EXPERIMENTS.md §Dry-run / §Roofline read from this output).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --pagerank   # graph workload rows
"""
import argparse
import json
import sys
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, get_config, list_configs, shape_applies
from ..models import LMModel
from ..models.model import batch_specs, cache_specs, input_specs, param_specs
from ..roofline.analysis import analyze, model_flops
from ..roofline.analytic import cost_for
from .mesh import HW, make_production_mesh

# --opt applies the EXPERIMENTS.md §Perf hillclimb lever set for the cell:
#   train cells  -> ZeRO-1 + sequence parallelism (+ pure-DP for small dense)
#   decode cells -> int8 KV cache + cache-T sharding over 'model'
_OPT_SMALL_DENSE = {"qwen2-1.5b", "smollm-360m", "qwen2-vl-2b", "qwen3-4b",
                    "rwkv6-1.6b", "recurrentgemma-2b"}


def _apply_opt(cfg, shape):
    import dataclasses
    if shape.kind == "train":
        if cfg.name in _OPT_SMALL_DENSE:
            # pure DP + ZeRO states + no grad accumulation: one weight pass
            # per step instead of 3·n_micro (weight re-reads dominate the
            # memory term once activations shrink to tokens/256 per device)
            return dataclasses.replace(cfg, pure_dp=True, zero1=True,
                                       grad_accum_dtype="bfloat16",
                                       microbatch=shape.global_batch)
        if cfg.moe is not None:
            moe = dataclasses.replace(cfg.moe, n_groups=8, group_top=4,
                                      capacity_factor=1.0,
                                      dispatch_dtype="float8_e4m3fn")
            return dataclasses.replace(cfg, zero1=True, seq_parallel=True,
                                       moe=moe)
        return dataclasses.replace(cfg, zero1=True, seq_parallel=True)
    if shape.kind == "decode":
        return dataclasses.replace(cfg, kv_cache_dtype="int8",
                                   shard_cache_t=True)
    return cfg


def _ns(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, mesh, *, verbose=True,
               opt=False):
    """Lower + compile one cell. Returns (compiled, RooflineReport)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applies(cfg, shape)
    if not ok:
        return None, why
    if opt:
        cfg = _apply_opt(cfg, shape)
    model = LMModel(cfg, mesh=mesh)
    aparams = model.abstract_params()
    pspecs = param_specs(cfg, aparams, mesh)
    chips = mesh.devices.size

    with mesh:
        if shape.kind == "train":
            aopt = jax.eval_shape(model.init_opt, aparams)
            ospecs = model.opt_partition(pspecs)
            bshapes, bspecs = batch_specs(cfg, mesh, shape.global_batch,
                                          shape.seq_len)
            fn = jax.jit(
                model.train_step,
                in_shardings=(_ns(mesh, pspecs), _ns(mesh, ospecs),
                              _ns(mesh, bspecs)),
                out_shardings=(_ns(mesh, pspecs), _ns(mesh, ospecs), None),
                donate_argnums=(0, 1))
            lowered = fn.lower(aparams, aopt, bshapes)
        elif shape.kind == "prefill":
            bshapes, bspecs = batch_specs(cfg, mesh, shape.global_batch,
                                          shape.seq_len)
            fn = jax.jit(model.prefill_step,
                         in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs)))
            lowered = fn.lower(aparams, bshapes)
        else:  # decode
            bshapes, bspecs = batch_specs(cfg, mesh, shape.global_batch, 1,
                                          decode=True)
            cshape, cspecs = cache_specs(cfg, mesh, shape.global_batch,
                                         shape.seq_len)
            fn = jax.jit(
                model.decode_step,
                in_shardings=(_ns(mesh, pspecs), _ns(mesh, cspecs),
                              _ns(mesh, bspecs), None),
                out_shardings=(None, _ns(mesh, cspecs)),
                donate_argnums=(1,))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = fn.lower(aparams, cshape, bshapes, pos)
        compiled = lowered.compile()

    tag = "/opt" if opt else ""
    rep = analyze(f"{arch}/{shape_name}/"
                  f"{'x'.join(map(str, mesh.devices.shape))}{tag}",
                  compiled, chips, model_flops(cfg, shape))
    # analytic trip-count-aware terms (see roofline/analytic.py docstring for
    # why the compiled cost_analysis alone is insufficient on this backend)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    cost = cost_for(cfg, shape, mesh_shape)
    rep.hlo_flops = cost.flops
    rep.hlo_bytes = cost.hbm_bytes * chips
    rep.coll_bytes = cost.coll_bytes
    rep.per_device_mem = cost.mem_bytes
    if verbose:
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        print(f"--- {rep.name} ---")
        print(f"  memory_analysis(raw): args={ma.argument_size_in_bytes/1e9:.2f}GB "
              f"temp={ma.temp_size_in_bytes/1e9:.2f}GB (loop-summed artifact; "
              f"see EXPERIMENTS.md)")
        print(f"  hlo-body(once-per-loop): flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  hlo collectives present: "
              f"{ {k: f'{v:.2e}' for k, v in rep.coll_breakdown.items() if v} }")
        print(f"  analytic: flops={cost.flops:.3e} hbm/dev={cost.hbm_bytes:.3e} "
              f"coll/dev={cost.coll_bytes:.3e} mem/dev={cost.mem_bytes/1e9:.2f}GB "
              f"fits={'YES' if cost.mem_bytes < HW.HBM_BYTES else 'NO'} "
              f"notes={cost.notes}")
        print(f"  terms(s): compute={rep.t_compute:.4f} "
              f"memory={rep.t_memory:.4f} collective={rep.t_collective:.4f} "
              f"-> bottleneck={rep.bottleneck} "
              f"roofline_frac={rep.roofline_fraction:.2f} "
              f"useful={rep.useful_ratio and round(rep.useful_ratio, 2)}")
    return compiled, rep


def lower_pagerank(mesh, n_vertices=1_048_576, d_p=64, tile=1024,
                   verbose=True, opt=False):
    """Dry-run the paper's workload itself on the production mesh: one DF-P
    iteration (all-gather + hybrid pull + fused update) at |V|=1M, |E|~16M."""
    from ..core.distributed import _FIELDS, _make_loop
    from ..core.pagerank import EllBlock, PRParams

    nd = mesh.devices.size
    n_loc = n_vertices // nd
    avg_deg = 16
    hi_cap = max(1, n_loc // 100)
    t_cap = hi_cap * 4
    shard = P(tuple(mesh.axis_names))
    # degree buckets a mean-degree-16 power-law block typically selects:
    # most rows at width 8/32, a thin tail at the d_p crossover width
    widths = sorted({w for w in (8, 32) if w < d_p} | {d_p})
    caps = [n_loc] + [max(1, n_loc // (4 ** i))
                      for i in range(1, len(widths))]
    buckets = tuple(
        EllBlock(rows=jax.ShapeDtypeStruct((nd, cap), jnp.int32),
                 idx=jax.ShapeDtypeStruct((nd, cap, w), jnp.int32),
                 mask=jax.ShapeDtypeStruct((nd, cap, w), jnp.float32))
        for w, cap in zip(widths, caps))
    sgd = {
        "buckets": buckets,
        "hi_pos": jax.ShapeDtypeStruct((nd, hi_cap), jnp.int32),
        "hi_tiles": jax.ShapeDtypeStruct((nd, t_cap, tile), jnp.int32),
        "hi_tmask": jax.ShapeDtypeStruct((nd, t_cap, tile), jnp.float32),
        "hi_rowmap": jax.ShapeDtypeStruct((nd, t_cap), jnp.int32),
        "out_deg": jax.ShapeDtypeStruct((nd, n_loc), jnp.int32),
        "valid": jax.ShapeDtypeStruct((nd, n_loc), jnp.bool_),
    }
    r = jax.ShapeDtypeStruct((nd, n_loc), jnp.float32)
    flags = jax.ShapeDtypeStruct((nd, n_loc), jnp.bool_)
    loop = _make_loop(tuple(mesh.axis_names), PRParams(max_iter=1),
                      n_vertices, dfp=True, compact_frontier=opt)
    fn = jax.shard_map(loop, mesh=mesh,
                       in_specs=({k: shard for k in _FIELDS}, shard, shard,
                                 shard),
                       out_specs=(shard, P()))
    with mesh:
        lowered = jax.jit(fn).lower(sgd, r, flags, flags)
        compiled = lowered.compile()
    edges = n_vertices * avg_deg
    rep = analyze(f"pagerank-dfp/{n_vertices}v/"
                  f"{'x'.join(map(str, mesh.devices.shape))}"
                  f"{'/opt' if opt else ''}",
                  compiled, nd, model_flops_val=2.0 * edges)
    if verbose:
        print(f"--- {rep.name} ---")
        print(f"  memory_analysis: {compiled.memory_analysis()}")
        print(f"  collectives: { {k: f'{v:.2e}' for k, v in rep.coll_breakdown.items() if v} }")
        print(f"  terms(s): compute={rep.t_compute:.6f} "
              f"memory={rep.t_memory:.6f} collective={rep.t_collective:.6f} "
              f"-> {rep.bottleneck}")
    return compiled, rep


def lower_pagerank_2d(mesh, n_vertices=1_048_576, d_p=8, verbose=True):
    """Beyond-paper 2-D edge partition (core/distributed2d.py): per-device
    gather shrinks from V to V/r bytes. Uses the trailing square
    (data, model) = (16, 16) sub-mesh; 'pod' (if present) replicates."""
    from ..core.distributed2d import Sharded2D, _loop_2d
    from ..core.pagerank import PRParams

    axes = mesh.axis_names
    row_axis, col_axis = axes[-2], axes[-1]
    r = mesh.shape[row_axis]
    c = mesh.shape[col_axis]
    rc = r * c
    n_pad = ((n_vertices + rc - 1) // rc) * rc
    v_r = n_pad // r
    blk = n_pad // rc
    shard = P((row_axis, col_axis))
    sgd = {
        "ell_idx": jax.ShapeDtypeStruct((rc, v_r, d_p), jnp.int32),
        "ell_mask": jax.ShapeDtypeStruct((rc, v_r, d_p), jnp.float32),
        "out_deg": jax.ShapeDtypeStruct((rc, blk), jnp.int32),
        "valid": jax.ShapeDtypeStruct((rc, blk), jnp.bool_),
    }
    rsh = jax.ShapeDtypeStruct((rc, blk), jnp.float32)
    fsh = jax.ShapeDtypeStruct((rc, blk), jnp.bool_)
    loop = _loop_2d(PRParams(max_iter=1), n_vertices, r, c, dfp=True,
                    row_axis=row_axis, col_axis=col_axis)
    fn = jax.shard_map(loop, mesh=mesh,
                       in_specs=({k: shard for k in sgd}, shard, shard, shard),
                       out_specs=(shard, P()))
    with mesh:
        compiled = jax.jit(fn).lower(sgd, rsh, fsh, fsh).compile()
    rep = analyze(f"pagerank-dfp-2d/{n_vertices}v/"
                  f"{'x'.join(map(str, mesh.devices.shape))}",
                  compiled, mesh.devices.size,
                  model_flops_val=2.0 * n_vertices * d_p)
    if verbose:
        print(f"--- {rep.name} ---")
        print(f"  memory_analysis: {compiled.memory_analysis()}")
        print(f"  collectives: { {k: f'{v:.2e}' for k, v in rep.coll_breakdown.items() if v} }")
        print(f"  terms(s): compute={rep.t_compute:.6f} "
              f"memory={rep.t_memory:.6f} collective={rep.t_collective:.6f} "
              f"-> {rep.bottleneck}")
    return compiled, rep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--pagerank", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="apply the hillclimb lever set (see §Perf)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    results = []
    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    for mesh in meshes:
        mesh_name = "x".join(map(str, mesh.devices.shape))
        if args.pagerank:
            _, rep = lower_pagerank(mesh, opt=args.opt)
            results.append(rep)
            if args.opt:
                _, rep2 = lower_pagerank_2d(mesh)
                results.append(rep2)
            continue
        archs = list_configs() if args.all or not args.arch else [args.arch]
        shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
        for arch in archs:
            for shape in shapes:
                try:
                    compiled, rep = lower_cell(arch, shape, mesh,
                                               opt=args.opt)
                    if compiled is None:
                        print(f"--- {arch}/{shape}/{mesh_name}: {rep}")
                        results.append({"name": f"{arch}/{shape}/{mesh_name}",
                                        "skip": rep})
                    else:
                        results.append(rep)
                        del compiled
                except Exception as e:
                    traceback.print_exc()
                    print(f"!!! {arch}/{shape}/{mesh_name} FAILED: {e}")
                    results.append({"name": f"{arch}/{shape}/{mesh_name}",
                                    "error": str(e)[:500]})

    if args.json:
        out = []
        for r in results:
            if isinstance(r, dict):
                out.append(r)
            else:
                out.append({
                    "name": r.name, "chips": r.chips,
                    "hlo_flops": r.hlo_flops, "hlo_bytes": r.hlo_bytes,
                    "coll_bytes": r.coll_bytes,
                    "coll_breakdown": r.coll_breakdown,
                    "model_flops": r.model_flops,
                    "t_compute": r.t_compute, "t_memory": r.t_memory,
                    "t_collective": r.t_collective,
                    "bottleneck": r.bottleneck,
                    "roofline_fraction": r.roofline_fraction,
                    "useful_ratio": r.useful_ratio,
                    "per_device_mem": r.per_device_mem,
                })
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    n_err = sum(1 for r in results if isinstance(r, dict) and "error" in r)
    print(f"\n== {len(results)} cells, {n_err} failures ==")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
