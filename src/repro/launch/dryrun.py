import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script:
  1. builds the production mesh (16,16) or (2,16,16) from placeholder
     host devices (the XLA_FLAGS line above MUST run before any jax import);
  2. resolves sharding rules, constructs ShapeDtypeStruct stand-ins for the
     train state / serve operands (zero allocation);
  3. ``jit(step).lower(...).compile()`` — proving the distribution config is
     coherent (sharding propagation, collective legality, memory fit);
  4. records memory_analysis / cost_analysis / per-class collective bytes
     (parsed from the partitioned HLO) and the three roofline terms into
     ``experiments/dryrun.json`` (incremental; reruns skip completed cells).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh multi
"""
import argparse
import gc
import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_arch, input_specs, decode_operand_specs
from repro.distributed.sharding import (
    batch_specs, cache_specs, param_specs, opt_state_specs, rules_for,
    tree_shardings,
)
from repro.launch.flops import model_flops, active_params
from repro.launch.hlo_costs import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import encdec, lm
from repro.models.config import SHAPES, ShapeSpec
from repro.train.optimizer import make_optimizer, warmup_cosine
from repro.train.train_step import TrainState, make_serve_step, make_train_step

# TPU v5e-ish hardware model (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

OUT_PATH = Path(__file__).resolve().parents[3] / "experiments" / "dryrun.json"


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------


def _serve_params_struct(cfg):
    """Parameter ShapeDtypeStructs in serving dtype (bf16)."""
    init_fn = encdec.init_params if cfg.family == "encdec" else lm.init_params
    shapes = jax.eval_shape(lambda k: init_fn(k, cfg), jax.random.key(0))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype
        ),
        shapes,
    )


def build_cell(arch_id: str, shape: ShapeSpec, mesh):
    """Returns (fn, arg_structs, in_shardings) for jit lowering."""
    arch = get_arch(arch_id)
    cfg = arch.config
    mode = "train" if shape.kind == "train" else shape.kind
    rules = rules_for(cfg, mesh, mode)
    if arch.dp_over_model:
        rules["batch"] = tuple(mesh.axis_names)

    def _valid_batch_prefix(size: int):
        axes = rules["batch"]
        axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
        names, prod = [], 1
        for a in axes:
            if size % (prod * mesh.shape[a]) == 0:
                names.append(a)
                prod *= mesh.shape[a]
            else:
                break
        return tuple(names), prod

    # sequence-sharded residuals for big dense/vlm training (keeps the
    # per-layer saved activations within HBM — DESIGN.md §6)
    from repro.launch.flops import active_params as _ap
    from repro.distributed.sharding import param_count_estimate, data_axes
    import dataclasses as _dc
    pcount = param_count_estimate(cfg)
    seq_ok = shape.seq_len % mesh.shape["model"] == 0
    if (
        cfg.family in ("dense", "vlm", "moe") and seq_ok
        and ((shape.kind == "train" and pcount >= 2e9)
             or (shape.kind == "prefill" and pcount >= 8e9))
    ):
        # sequence-sharded residuals (Megatron-SP style): per-layer saved
        # activations and attention scores shard over the model axis.
        cfg = _dc.replace(cfg, act_shard_spec=(data_axes(mesh), "model", None))
    else:
        # pin the residual's batch sharding through the layer/ssm scan
        # carries (observed: GSPMD drops batch sharding inside carries for
        # scan-heavy families and long prefills).  Use the longest mesh-axis
        # prefix that divides the per-call batch (microbatch for train).
        accum_eff = 1
        if shape.kind == "train":
            _, dshards = _valid_batch_prefix(shape.global_batch)
            accum_eff = max(1, min(arch.grad_accum, shape.global_batch // max(dshards, 1)))
        per_call = shape.global_batch // accum_eff
        names, _ = _valid_batch_prefix(per_call)
        if names:
            entry = names[0] if len(names) == 1 else tuple(names)
            cfg = _dc.replace(cfg, act_shard_spec=(entry, None, None))
    if (
        shape.kind == "train" and pcount >= 2e9
        and cfg.family in ("dense", "vlm", "moe")
        and cfg.d_model % mesh.shape["model"] == 0
        and cfg.d_model % mesh.shape["data"] == 0
        and cfg.d_ff % mesh.shape["model"] == 0
    ):
        # custom-VJP grad sharding (see models/pmm.py)
        cfg = _dc.replace(
            cfg, grad_shard=True,
            mesh_data_size=mesh.shape["data"],
            mesh_model_size=mesh.shape["model"],
        )
    if cfg.family == "moe" and cfg.n_experts % mesh.shape["model"] == 0:
        cfg = _dc.replace(cfg, moe_ep_shard=True)

    if shape.kind == "train":
        optimizer = make_optimizer(arch.optimizer, warmup_cosine(arch.peak_lr))
        state_struct = jax.eval_shape(
            lambda k: _init_state_for(cfg, optimizer, k), jax.random.key(0)
        )
        pspecs = param_specs(state_struct.params, cfg, mesh, rules)
        ospecs = opt_state_specs(state_struct.opt_state, pspecs, state_struct.params, mesh)
        state_specs = TrainState(P(), pspecs, ospecs)
        batch = input_specs(cfg, shape)
        bspecs = batch_specs(batch, mesh, rules)
        _, dshards = _valid_batch_prefix(shape.global_batch)
        accum = max(1, min(arch.grad_accum, shape.global_batch // max(dshards, 1)))
        baxes = rules["batch"]
        baxes = (baxes,) if isinstance(baxes, str) else tuple(baxes or ())
        step = make_train_step(
            cfg, optimizer, accum_steps=accum,
            batch_axes=tuple((a, mesh.shape[a]) for a in baxes),
        )
        in_sh = (tree_shardings(state_specs, mesh), tree_shardings(bspecs, mesh))
        out_sh = (tree_shardings(state_specs, mesh), None)
        # donate the train state: params/opt buffers update in place
        return step, (state_struct, batch), in_sh, out_sh, (0,)

    params = _serve_params_struct(cfg)
    pspecs = param_specs(params, cfg, mesh, rules)
    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        bspecs = batch_specs(batch, mesh, rules)
        step = make_serve_step(cfg, "prefill", max_len=None)
        in_sh = (tree_shardings(pspecs, mesh), tree_shardings(bspecs, mesh))
        # pin the output cache layout (otherwise GSPMD may replicate it)
        with mesh:
            out_struct = jax.eval_shape(step, params, batch)
        ocspecs = cache_specs(out_struct[1], cfg, mesh, rules)
        out_sh = (None, tree_shardings(ocspecs, mesh))
        return step, (params, batch), in_sh, out_sh, ()

    # decode: donate the KV cache / state (updated in place)
    cache, token, pos, pos_ref = decode_operand_specs(cfg, shape)
    cspecs = cache_specs(cache, cfg, mesh, rules)
    tspec = batch_specs({"t": token}, mesh, rules)["t"]
    step = make_serve_step(cfg, "decode")
    in_sh = (
        tree_shardings(pspecs, mesh),
        tree_shardings(cspecs, mesh),
        NamedSharding(mesh, tspec),
        NamedSharding(mesh, P()),
    )
    out_sh = (None, tree_shardings(cspecs, mesh))
    return step, (params, cache, token, pos), in_sh, out_sh, (1,)


def _init_state_for(cfg, optimizer, key):
    init_fn = encdec.init_params if cfg.family == "encdec" else lm.init_params
    params = init_fn(key, cfg)
    return TrainState(jnp.zeros((), jnp.int32), params, optimizer.init(params))


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------


def roofline(hcost, n_chips, cfg, shape):
    flops_dev = float(hcost.flops)
    bytes_dev = float(hcost.bytes)
    coll_dev = float(hcost.collective_bytes)
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_dev / ICI_BW
    mflops = model_flops(cfg, shape)
    t_model = mflops / (n_chips * PEAK_FLOPS)
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    bound = max(t_compute, t_memory, t_coll)
    return {
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_coll,
        "dominant": dominant,
        "model_flops_global": mflops,
        "hlo_flops_per_dev": flops_dev,
        "hlo_bytes_per_dev": bytes_dev,
        "collective_bytes_per_dev": coll_dev,
        "useful_flops_ratio": mflops / max(flops_dev * n_chips, 1.0),
        "roofline_fraction": t_model / max(bound, 1e-12),
    }


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_cell(arch_id: str, shape: ShapeSpec, multi_pod: bool, verbose=True):
    arch = get_arch(arch_id)
    reason = arch.skip_reason(shape.name)
    if reason:
        return {"status": "skipped", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    fn, args, in_sh, out_sh, donate = build_cell(arch_id, shape, mesh)
    jit_kwargs = {"in_shardings": in_sh, "donate_argnums": donate}
    if out_sh is not None:
        jit_kwargs["out_shardings"] = out_sh
    with mesh:
        lowered = jax.jit(fn, **jit_kwargs).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    hcost = analyze_hlo(hlo)
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_per_device_gb": (
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes
        ) / 1e9,
    }
    cfg = arch.config
    result = {
        "status": "ok",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem,
        "collectives": {
            k: v for k, v in hcost.collective_stats.items() if v["count"]
        },
        "n_while": hcost.n_while,
        "trip_counts": hcost.trip_counts,
        "xla_cost_analysis": {  # cross-check only (undercounts loop bodies)
            "flops": float(xla_cost.get("flops", 0.0)),
            "bytes_accessed": float(xla_cost.get("bytes accessed", 0.0)),
        },
        "roofline": roofline(hcost, n_chips, cfg, shape),
        "fits_16gb": mem["peak_per_device_gb"] <= 16.0,
    }
    if verbose:
        r = result["roofline"]
        print(
            f"  [{result['mesh']}] {arch_id} × {shape.name}: "
            f"compile {t_compile:.0f}s, peak {mem['peak_per_device_gb']:.2f} GB/dev, "
            f"compute {r['compute_s']*1e3:.2f}ms / memory {r['memory_s']*1e3:.2f}ms / "
            f"coll {r['collective_s']*1e3:.2f}ms → {r['dominant']}-bound, "
            f"roofline_frac {r['roofline_fraction']:.3f}", flush=True,
        )
    del compiled, lowered, fn, args
    gc.collect()
    return result


def _shape_for(arch_id, shape: ShapeSpec) -> ShapeSpec:
    return shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--out", default=str(OUT_PATH))
    args = ap.parse_args()

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [s for s in SHAPES if args.shape in (None, s.name)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch_id in archs:
        for shape in shapes:
            for multi in meshes:
                key = f"{arch_id}|{shape.name}|{'multi' if multi else 'single'}"
                if key in results and results[key].get("status") in ("ok", "skipped") \
                        and not args.force:
                    continue
                print(f"cell {key} ...", flush=True)
                try:
                    results[key] = run_cell(arch_id, shape, multi)
                except Exception as e:  # noqa: BLE001 — record and continue
                    results[key] = {"status": "failed", "error": f"{type(e).__name__}: {e}"}
                    print(f"  FAILED: {type(e).__name__}: {e}", flush=True)
                st = results[key]["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_fail += st == "failed"
                out_path.write_text(json.dumps(results, indent=1))
    total_ok = sum(1 for v in results.values() if v["status"] == "ok")
    total_skip = sum(1 for v in results.values() if v["status"] == "skipped")
    total_fail = sum(1 for v in results.values() if v["status"] == "failed")
    print(f"\ndry-run complete: {total_ok} ok, {total_skip} skipped, {total_fail} failed "
          f"(of {len(results)} cells) → {out_path}")
    if total_fail:
        for k, v in results.items():
            if v["status"] == "failed":
                print(f"  FAIL {k}: {v['error'][:200]}")


if __name__ == "__main__":
    main()
