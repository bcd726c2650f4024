import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run + roofline extraction (deliverables (e) and (g)).

For every (architecture x input shape) cell:
  1. PRODUCTION program (layer scans rolled): ``jit(step).lower().compile()``
     on the single-pod (16x16) and multi-pod (2x16x16) meshes -> proves the
     distribution config is coherent; records ``memory_analysis()``.
  2. ROOFLINE probes (single-pod mesh): XLA's cost analysis counts while-loop
     bodies ONCE (verified 8x undercount on an 8-step scan), so per-layer
     unit costs are measured on depth-reduced *unrolled* probe configs and
     extrapolated linearly to full depth:
         cost(full) = cost(probe_a) + (units_full - units_a) * d_cost/d_unit
     Attention chunk scans are unrolled too (probe chunk sizes chosen so the
     total FLOPs equal the production program's). SSD keeps its production
     chunk (its heavy einsums are outside the carry scan, so they are counted
     correctly).

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-32b --shape train_4k
  python -m repro.launch.dryrun --all --out benchmarks/results/dryrun.json
"""
import argparse
import functools
import json
import time
import traceback
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..analysis.roofline import collective_bytes, model_flops_estimate, roofline_terms
from ..configs import ARCH_IDS, SHAPES_BY_NAME, get_config, shape_skips
from ..configs.base import ArchConfig, ShapeConfig
from ..models import attention as attention_mod
from ..models import lm
from ..sharding import cache_specs, param_specs, set_mesh_ctx
from ..train.step import init_train_state, make_train_step
from .mesh import as_auto_mesh, make_production_mesh


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    B, T = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        if cfg.frontend == "token":
            inp = jax.ShapeDtypeStruct((B, T), jnp.int32)
        else:  # stub modality frontend: precomputed frame/patch embeddings
            inp = jax.ShapeDtypeStruct((B, T, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            return {"inputs": inp, "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
        return {"inputs": inp}
    # decode: one new token against a T-long cache
    state = jax.eval_shape(
        functools.partial(lm.init_decode_state, cfg, B, T))
    return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32), "state": state}


def _batch_sharding(mesh, sds_tree):
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def spec(sds):
        want = [baxes] + [None] * (len(sds.shape) - 1)
        from ..sharding import resolve_spec
        return NamedSharding(mesh, resolve_spec(sds.shape, want, mesh))

    return jax.tree.map(spec, sds_tree)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------
def build_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, *, chunks=None):
    """Returns (fn, args_sds tuple, in_shardings, out_shardings)."""
    chunks = chunks or {}
    q = chunks.get("q_chunk", 512)
    kv = chunks.get("kv_chunk", 512)
    lc = chunks.get("loss_chunk", 512)
    sc = chunks.get("ssd_chunk", 128)
    mb = chunks.get("microbatch", None)
    specs = input_specs(cfg, shape)
    key = jax.random.PRNGKey(0)

    if shape.kind == "train":
        state_sds = jax.eval_shape(functools.partial(init_train_state, cfg), key)
        state_sh = param_specs(state_sds, mesh)
        batch_sh = _batch_sharding(mesh, specs)
        fn = make_train_step(cfg, loss_chunk=lc, q_chunk=q, kv_chunk=kv,
                             ssd_chunk=sc, microbatch=mb)
        return fn, (state_sds, specs), ((state_sh, batch_sh)), (state_sh, None)

    params_sds = jax.eval_shape(functools.partial(lm.init_lm, cfg), key)
    params_sh = param_specs(params_sds, mesh)
    if shape.kind == "prefill":
        fn = functools.partial(lm.prefill_forward, cfg, q_chunk=q, kv_chunk=kv,
                               ssd_chunk=sc)
        in_sh = (params_sh, _batch_sharding(mesh, specs["inputs"]))
        return fn, (params_sds, specs["inputs"]), in_sh, None

    # decode
    state_sds = specs["state"]
    state_sh = cache_specs(state_sds, mesh)

    def fn(params, state, tokens):
        logits, st = lm.decode_step(cfg, params, tokens, state)
        return jnp.argmax(logits, -1).astype(jnp.int32), st

    tok_sh = _batch_sharding(mesh, specs["tokens"])
    return (fn, (params_sds, state_sds, specs["tokens"]),
            (params_sh, state_sh, tok_sh), (None, state_sh))


def lower_and_compile(cfg, shape, mesh, *, chunks=None, unroll=False):
    mesh = as_auto_mesh(mesh)
    lm.SCAN_UNROLL = unroll
    attention_mod.SCAN_UNROLL = unroll
    set_mesh_ctx(mesh)
    # optimized-default (§Perf): grouped MoE dispatch, one group per data shard
    from ..models import moe as moe_mod
    prev_groups = moe_mod.DISPATCH_GROUPS
    if moe_mod.DISPATCH_GROUPS == 1:
        moe_mod.DISPATCH_GROUPS = dict(zip(mesh.axis_names,
                                           mesh.devices.shape)).get("data", 1)
    try:
        fn, args, in_sh, out_sh = build_cell(cfg, shape, mesh, chunks=chunks)
        t0 = time.time()
        with mesh:
            jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jfn.lower(*args)
            compiled = lowered.compile()
        dt = time.time() - t0
        return lowered, compiled, dt
    finally:
        lm.SCAN_UNROLL = False
        attention_mod.SCAN_UNROLL = False
        moe_mod.DISPATCH_GROUPS = prev_groups
        set_mesh_ctx(None)


# ---------------------------------------------------------------------------
# model-FLOPs accounting (6*N_active*D)
# ---------------------------------------------------------------------------
def active_param_count(cfg: ArchConfig) -> float:
    params = jax.eval_shape(functools.partial(lm.init_lm, cfg), jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    total = 0.0
    for path, leaf in flat:
        pstr = ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        n = float(np.prod(leaf.shape))
        if ".moe." in pstr and any(pstr.endswith(s) for s in ("wi", "wg", "wo")):
            n *= cfg.top_k / cfg.n_experts   # routed experts: only top-k active
        if "embed" in pstr or "head" in pstr:
            continue                          # embedding lookups are not matmul FLOPs
        total += n
    return total


# ---------------------------------------------------------------------------
# roofline probes (depth extrapolation)
# ---------------------------------------------------------------------------
def _probe_plan(cfg: ArchConfig):
    """[(probe_cfg, units)] + full_units; cost is linear in ``units``."""
    if cfg.family == "hybrid":
        n_groups, g, tail = cfg.n_layers // cfg.hybrid_group, cfg.hybrid_group, \
            cfg.n_layers % cfg.hybrid_group
        # 3 probes solve (fixed, per_mamba, per_shared); see solver below
        return "hybrid", [
            cfg.replace(n_layers=3, hybrid_group=3),   # 1 shared + 3 mamba
            cfg.replace(n_layers=6, hybrid_group=6),   # 1 shared + 6 mamba
            cfg.replace(n_layers=6, hybrid_group=3),   # 2 shared + 6 mamba
        ], (n_groups, cfg.n_layers)
    if cfg.local_global_period == 2:
        return "linear", [cfg.replace(n_layers=2), cfg.replace(n_layers=4)], \
            cfg.n_layers // 2  # units = pairs
    if cfg.family == "moe" and cfg.first_dense_layers:
        nd = cfg.first_dense_layers
        return "linear", [cfg.replace(n_layers=nd + 1), cfg.replace(n_layers=nd + 2)], \
            cfg.n_layers - nd  # units = moe layers
    return "linear", [cfg.replace(n_layers=1), cfg.replace(n_layers=2)], cfg.n_layers


def _cost_vector(compiled, lowered=None) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older jax: one dict per computation
        ca = ca[0] if ca else {}
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
        **{f"coll_{k}": float(v) for k, v in coll.items()},
    }


def probe_roofline(cfg: ArchConfig, shape: ShapeConfig, mesh) -> Dict[str, float]:
    """Extrapolated full-depth per-device cost vector."""
    # probe chunk sizes: keep total FLOPs identical to production while
    # bounding unrolled body count (full-attention FLOPs are chunk-invariant)
    T = shape.seq_len
    chunks = {"q_chunk": min(4096, T), "kv_chunk": min(4096, T),
              "loss_chunk": min(4096, T), "ssd_chunk": 128}
    kind, probes, full = _probe_plan(cfg)
    vecs = []
    for pc in probes:
        _, compiled, dt = lower_and_compile(pc, shape, mesh, chunks=chunks,
                                            unroll=True)
        vecs.append(_cost_vector(compiled))
    keys = sorted(set().union(*[set(v) for v in vecs]))

    out = {}
    if kind == "linear":
        (ca, ua), (cb, ub) = (vecs[0], 1), (vecs[1], 2)
        for k in keys:
            per = (cb.get(k, 0.0) - ca.get(k, 0.0)) / (ub - ua)
            out[k] = ca.get(k, 0.0) + (full - ua) * per
    else:  # hybrid: cA = f + s + 3m ; cB = f + s + 6m ; cC = f + 2s + 6m
        cA, cB, cC = vecs
        n_shared, n_mamba = full
        for k in keys:
            m = (cB.get(k, 0.0) - cA.get(k, 0.0)) / 3.0
            s = cC.get(k, 0.0) - cB.get(k, 0.0)
            f = cA.get(k, 0.0) - s - 3 * m
            out[k] = f + n_shared * s + n_mamba * m
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, *, do_multipod=True, do_roofline=True
             ) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "kind": shape.kind}
    skip = shape_skips(cfg, shape)
    if skip:
        rec["skipped"] = skip
        return rec

    meshes = [("pod16x16", make_production_mesh(multi_pod=False))]
    if do_multipod:
        meshes.append(("multipod2x16x16", make_production_mesh(multi_pod=True)))

    for mname, mesh in meshes:
        chips = int(np.prod(list(mesh.shape.values())))
        lowered, compiled, dt = lower_and_compile(cfg, shape, mesh)
        ma = compiled.memory_analysis()
        print(f"[dryrun] {arch} x {shape_name} x {mname}: compile {dt:.1f}s")
        print(f"         memory_analysis: args={ma.argument_size_in_bytes/1e9:.3f}GB "
              f"out={ma.output_size_in_bytes/1e9:.3f}GB "
              f"temp={ma.temp_size_in_bytes/1e9:.3f}GB (per device)")
        cv = _cost_vector(compiled)
        print(f"         rolled-scan cost (body-once): flops={cv['flops']:.3e} "
              f"bytes={cv['bytes']:.3e} coll={cv['coll']:.3e}")
        rec[mname] = {
            "compile_s": dt,
            "argument_bytes_per_dev": ma.argument_size_in_bytes,
            "output_bytes_per_dev": ma.output_size_in_bytes,
            "temp_bytes_per_dev": ma.temp_size_in_bytes,
            "rolled_cost": cv,
            "chips": chips,
        }

    if do_roofline:
        mesh = make_production_mesh(multi_pod=False)
        chips = 256
        full_cost = probe_roofline(cfg, shape, mesh)
        n_act = active_param_count(cfg)
        tokens = (shape.global_batch * shape.seq_len
                  if shape.kind in ("train", "prefill") else shape.global_batch)
        mf = model_flops_estimate(n_act, tokens,
                                  "train" if shape.kind == "train" else "infer")
        rl = roofline_terms({"flops": full_cost["flops"],
                             "bytes accessed": full_cost["bytes"]},
                            "", chips=chips, model_flops=mf)
        # collective bytes already summed in probe extrapolation
        rl.bytes_coll = full_cost["coll"]
        rl.collective_s = full_cost["coll"] / 50e9
        terms = {"compute": rl.compute_s, "memory": rl.memory_s,
                 "collective": rl.collective_s}
        rl.bottleneck = max(terms, key=terms.get)
        rec["roofline"] = {**rl.to_row(),
                           "coll_breakdown": {k[5:]: v for k, v in full_cost.items()
                                              if k.startswith("coll_")},
                           "active_params": n_act, "tokens": tokens}
        print(f"         roofline: compute={rl.compute_s*1e3:.2f}ms "
              f"memory={rl.memory_s*1e3:.2f}ms collective={rl.collective_s*1e3:.2f}ms "
              f"-> {rl.bottleneck}-bound; useful={rl.useful_ratio:.2f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-multipod", action="store_true")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES_BY_NAME:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    results = []
    for a, s in cells:
        try:
            results.append(run_cell(a, s, do_multipod=not args.no_multipod,
                                    do_roofline=not args.no_roofline))
        except Exception as e:  # a failing cell is a bug — record loudly
            traceback.print_exc()
            results.append({"arch": a, "shape": s, "error": repr(e)})
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    # merge with existing results (per-cell reruns update in place)
    merged: Dict[Tuple[str, str], Dict] = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            for r in json.load(f):
                merged[(r["arch"], r["shape"])] = r
    for r in results:
        merged[(r["arch"], r["shape"])] = r
    with open(args.out, "w") as f:
        json.dump(list(merged.values()), f, indent=1)
    n_err = sum("error" in r for r in results)
    print(f"[dryrun] wrote {args.out}; {len(results)} cells, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
