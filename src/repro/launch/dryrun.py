import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# (Override for small integration tests via REPRO_DRYRUN_DEVICES.)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape) cell, on the single-pod 16×16 mesh
and the 2×16×16 multi-pod mesh:

    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=…, donate…).lower(*input_specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

plus HLO collective parsing → artifacts/dryrun/<arch>__<shape>__<mesh>.json
consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # single-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.models.transformer import LM
from repro.sharding import SHAPES, cell_runnable, input_specs, make_plan
from repro.sharding.planner import data_axes
from repro.launch.mesh import make_production_mesh
from repro.launch import hlo_analysis as H
from repro.launch import hlo_loops as HL
from repro.training.train_step import (abstract_train_state, make_train_step,
                                       train_state_specs)

ASSIGNED = tuple(a for a in ARCH_IDS if a != "edge-tiny")


def _shard(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_shardings(mesh, plan, batch_specs):
    return {k: NamedSharding(mesh, plan.batch_specs.get(k, P()))
            for k in batch_specs}


def lower_cell(arch: str, shape_name: str, mesh, *, scale: float = 1.0,
               overrides=None, hlo_out: str | None = None):
    """Build + lower + compile one cell. Returns (record, compiled)."""
    cfg = get_config(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    ok, reason = cell_runnable(cfg, shape_name)
    if not ok:
        return {"status": "skipped", "reason": reason}, None

    cell, batch, seq, specs = input_specs(cfg, shape_name, scale=scale)
    lm = LM(cfg)
    n_dev = mesh.devices.size
    t0 = time.time()

    if cell.kind == "train":
        state_abs = abstract_train_state(lm)
        plan = make_plan(cfg, mesh, "train", batch=batch, seq=seq,
                         param_tree=state_abs.params)
        step = make_train_step(lm, microbatches=plan.microbatches)
        state_specs = train_state_specs(plan, state_abs)
        in_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                              is_leaf=lambda x: isinstance(x, P)),
                 _batch_shardings(mesh, plan, specs))
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=in_sh,
                              donate_argnums=(0,)).lower(state_abs, specs)
    elif cell.kind == "prefill":
        params_abs = lm.param_specs()
        max_len = seq
        cache_abs = lm.init_cache(batch, max_len, abstract=True)
        plan = make_plan(cfg, mesh, "prefill", batch=batch, seq=seq,
                         param_tree=params_abs, cache_tree=cache_abs)

        def prefill_step(params, b):
            return lm.prefill(params, b, max_len)

        in_sh = (_shard(mesh, plan.param_specs),
                 _batch_shardings(mesh, plan, specs))
        # the output cache is the session state: shard it like the decode
        # cache, else XLA leaves it batch-sharded only (13 GB/device observed)
        out_sh = (NamedSharding(mesh, P()), _shard(mesh, plan.cache_specs))
        with jax.set_mesh(mesh):
            lowered = jax.jit(prefill_step, in_shardings=in_sh,
                              out_shardings=out_sh).lower(params_abs, specs)
    else:  # decode / serve_step
        params_abs = lm.param_specs()
        if cfg.serve_weight_dtype == "int8":
            from repro.models.quant import abstract_quantize_tree
            params_abs = abstract_quantize_tree(params_abs)
        cache_abs = lm.init_cache(batch, seq, abstract=True)
        plan = make_plan(cfg, mesh, "decode", batch=batch, seq=seq,
                         param_tree=params_abs, cache_tree=cache_abs)

        def serve_step(params, cache, tokens):
            return lm.decode_step(params, cache, tokens)

        in_sh = (_shard(mesh, plan.param_specs),
                 _shard(mesh, plan.cache_specs),
                 NamedSharding(mesh, plan.batch_specs["tokens"]))
        with jax.set_mesh(mesh):
            lowered = jax.jit(serve_step, in_shardings=in_sh,
                              donate_argnums=(1,)).lower(
                                  params_abs, cache_abs, specs["tokens"])

    lower_s = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t1

    ca = H.normalize_cost_analysis(compiled.cost_analysis())
    ma = compiled.memory_analysis()
    print(ma)
    print({k: ca.get(k) for k in ("flops", "bytes accessed")})
    hlo_text = compiled.as_text()
    if hlo_out:
        with gzip.open(hlo_out, "wt", compresslevel=5) as f:
            f.write(hlo_text)
    colls = H.collect_collectives(hlo_text, n_dev)
    roof_naive = H.roofline_terms(ca, colls, n_dev)
    # loop-aware analysis: XLA's cost_analysis counts while bodies once —
    # scan-shaped programs need trip-count multipliers (repro.launch.hlo_loops)
    la = HL.analyze(hlo_text, n_dev)
    roof = {
        "flops_per_device": la["flops_per_device"],
        "flops_global": la["flops_per_device"] * n_dev,
        "hbm_bytes_per_device": la["hbm_bytes_per_device"],
        "wire_bytes_per_device": la["wire_bytes_per_device"],
        "compute_s": la["flops_per_device"] / H.PEAK_FLOPS,
        "memory_s": la["hbm_bytes_per_device"] / H.HBM_BW,
        "collective_s": la["wire_bytes_per_device"] / H.LINK_BW,
    }
    roof["dominant"] = max(
        (("compute", roof["compute_s"]), ("memory", roof["memory_s"]),
         ("collective", roof["collective_s"])), key=lambda kv: kv[1])[0]
    roof["roofline_bound_s"] = max(roof["compute_s"], roof["memory_s"],
                                   roof["collective_s"])
    roof["compute_fraction_of_bound"] = (
        roof["compute_s"] / roof["roofline_bound_s"]
        if roof["roofline_bound_s"] else 0.0)
    mf = H.model_flops(cfg, cell.kind, batch, seq)
    record = {
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "kind": cell.kind,
        "mesh": {"shape": list(mesh.devices.shape),
                 "axes": list(mesh.axis_names), "devices": int(n_dev)},
        "batch": batch,
        "seq": seq,
        "scale": scale,
        "microbatches": getattr(plan, "microbatches", 1),
        "plan_notes": plan.notes,
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
        "memory": H.memory_report(ma),
        "cost_analysis": {k: float(v) for k, v in ca.items()
                          if isinstance(v, (int, float))},
        "collectives": la["collectives_per_op"],
        "roofline": roof,
        "roofline_naive_bodyonce": roof_naive,
        "model_flops": mf,
        "useful_flops_ratio": (mf / roof["flops_global"]
                               if roof["flops_global"] else 0.0),
    }
    return record, compiled


def run_cell(arch, shape_name, *, multi_pod=False, scale=1.0, out_dir=None,
             force=False, overrides=None, tag=""):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out_dir = out_dir or "artifacts/dryrun"
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    path = os.path.join(out_dir, stem + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        record, _ = lower_cell(arch, shape_name, mesh, scale=scale,
                               overrides=overrides,
                               hlo_out=path.replace(".json", ".hlo.txt.gz"))
    except Exception as e:  # a failure here is a bug in the system
        record = {"status": "error", "arch": arch, "shape": shape_name,
                  "mesh": mesh_name, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    record.setdefault("arch", arch)
    record.setdefault("shape", shape_name)
    record["mesh_name"] = mesh_name
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=float)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = []
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            cells.append((a, s))
    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for mp in meshes:
        for a, s in cells:
            t0 = time.time()
            rec = run_cell(a, s, multi_pod=mp, scale=args.scale,
                           out_dir=args.out, force=args.force)
            status = rec["status"]
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (f"dom={r['dominant']:<10} "
                         f"bound={r['roofline_bound_s']*1e3:8.2f}ms "
                         f"fit={rec['memory']['fits_hbm']}")
            elif status == "error":
                failures += 1
                extra = rec["error"][:120]
            print(f"[{'2x16x16' if mp else '16x16'}] {a:22s} {s:12s} "
                  f"{status:8s} {time.time()-t0:6.1f}s {extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run cells FAILED")


if __name__ == "__main__":
    main()
