"""Kernel micro-benchmarks + Gen-DST convergence timing (paper §3.3).

Times the XLA reference paths (the production CPU-measurable numbers) and
validates the Pallas kernels, which run in interpret mode on the CPU backend
and compile anywhere else (``interpret=None``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gen_dst import GenDSTConfig, gen_dst
from repro.core.measures import factorize
from repro.kernels.entropy.ref import masked_histogram_ref
from repro.kernels.entropy.kernel import masked_histogram_pallas
from repro.kernels.flash_attention.ref import attention_ref


def _time(fn, *args, reps=5):
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def main():
    rows = []
    rng = np.random.default_rng(0)

    # masked histogram (Gen-DST fitness primitive)
    for N, M, B in [(10_000, 23, 256), (100_000, 23, 256), (1_000_000, 15, 256)]:
        codes = jnp.asarray(rng.integers(0, B, (N, M)), jnp.int32)
        w = jnp.asarray((rng.random(N) < 0.01).astype(np.float32))
        us = _time(lambda c, ww: masked_histogram_ref(c, ww, B), codes, w)
        rows.append((f"masked_hist_ref_{N}x{M}", us, f"{N*M/us:.0f} cells/us"))

    # pallas kernel correctness spot (interpret mode, small)
    codes = jnp.asarray(rng.integers(0, 64, (2048, 8)), jnp.int32)
    w = jnp.ones((2048,), jnp.float32)
    t0 = time.perf_counter()
    hk = masked_histogram_pallas(codes, w, 64)
    hr = masked_histogram_ref(codes, w, 64)
    ok = bool(jnp.abs(hk - hr).max() < 1e-3)
    rows.append(("masked_hist_pallas_interp_ok", (time.perf_counter() - t0) * 1e6,
                 f"allclose={ok}"))

    # Gen-DST end-to-end (paper default config on a 100k-row dataset)
    X = np.column_stack([rng.integers(0, k, 100_000)
                         for k in (3, 5, 17, 2, 40, 7, 200, 11)]).astype(float)
    y = rng.integers(0, 2, 100_000).astype(float)
    coded = factorize(X, y)
    t0 = time.perf_counter()
    res = gen_dst(jax.random.key(0), coded, cfg=GenDSTConfig(psi=30, phi=100))
    jax.block_until_ready(res.fitness)
    t_total = time.perf_counter() - t0
    rows.append(("gen_dst_100k_default", t_total * 1e6,
                 f"loss={-float(res.fitness):.5f}"))
    # steady-state (post-compile) generation rate
    t0 = time.perf_counter()
    res = gen_dst(jax.random.key(1), coded, cfg=GenDSTConfig(psi=30, phi=100))
    jax.block_until_ready(res.fitness)
    rows.append(("gen_dst_100k_steady", (time.perf_counter() - t0) * 1e6,
                 f"{30 / max(time.perf_counter() - t0, 1e-9):.1f} gen/s"))

    # attention reference (XLA path used in the dry-run)
    q = jnp.asarray(rng.normal(0, 1, (1, 1024, 8, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (1, 1024, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (1, 1024, 2, 64)), jnp.bfloat16)
    us = _time(lambda a, b, c: attention_ref(a, b, c, causal=True), q, k, v)
    flops = 4 * 1024 * 1024 * 8 * 64 / 2
    rows.append(("attention_ref_1k_gqa", us, f"{flops/us/1e6:.1f} GFLOP/s"))

    return rows


def gen_dst_rows(N=100_000, psi=24, phi=100, cross_every=4, quick_tag="100k"):
    """Generation-step timing: incremental fitness vs full recompute, islands.

    The incremental-vs-full pair shares the GA trajectory bit-for-bit (same
    key, same cadence), so the speedup isolates exactly the fitness-path
    change (DESIGN.md §5.5).  Acceptance target: >=2x at N>=100k.
    """
    rng = np.random.default_rng(0)
    X = np.column_stack([rng.integers(0, k, N)
                         for k in (3, 5, 17, 2, 40, 7, 200, 11)]).astype(float)
    y = rng.integers(0, 2, N).astype(float)
    coded = factorize(X, y)

    def run(cfg, key=1):
        res = gen_dst(jax.random.key(0), coded, cfg=cfg)   # warmup/compile
        jax.block_until_ready(res.fitness)
        t0 = time.perf_counter()
        res = gen_dst(jax.random.key(key), coded, cfg=cfg)
        jax.block_until_ready(res.fitness)
        return (time.perf_counter() - t0) / cfg.psi * 1e6, res  # us/generation

    rows = []
    cfg = GenDSTConfig(psi=psi, phi=phi, cross_every=cross_every)
    us_full, r_full = run(cfg._replace(incremental=False))
    us_inc, r_inc = run(cfg)
    assert float(r_full.fitness) == float(r_inc.fitness), "parity broken"
    rows.append((f"gen_dst_step_full_{quick_tag}", us_full,
                 f"loss={-float(r_full.fitness):.5f}"))
    rows.append((f"gen_dst_step_incremental_{quick_tag}", us_inc,
                 f"speedup={us_full / us_inc:.2f}x"))

    isl = GenDSTConfig(psi=psi, phi=max(2, phi // 4) // 2 * 2, num_islands=4,
                       migrate_every=5, cross_every=cross_every)
    us_isl, r_isl = run(isl)
    rows.append((f"gen_dst_step_islands4_{quick_tag}", us_isl,
                 f"loss={-float(r_isl.fitness):.5f}"))
    return rows


def gen_dst_fused_rows(N=20_000, psi=6, phi=16, quick_tag="20k"):
    """Per-generation timing of the fused backend (DESIGN.md §16).

    Two regimes, each timed for ``backend="jnp"`` and ``"pallas_fused"``
    with the same key so the trajectories are bit-identical (asserted):
    ``delta`` (cross_every=4 — 3 of 4 generations are one-row delta
    updates) and ``full`` (cross_every=1 — every generation rebuilds the
    histograms).  On CPU the Pallas leg runs in *interpret mode*: the
    timing validates semantics and recompile hygiene, not speed — the
    compiled number needs a real TPU.  The derived column carries the
    analytic useful/launched FLOPs ratio (``launch/flops.py``), the
    padding+one-hot-materialization overhead a TPU roofline would see.
    """
    from repro.launch.flops import gen_dst_generation_flops

    rng = np.random.default_rng(0)
    X = np.column_stack([rng.integers(0, k, N)
                         for k in (3, 5, 17, 2, 40, 7, 200, 11)]).astype(float)
    y = rng.integers(0, 2, N).astype(float)
    coded = factorize(X, y)
    n = max(2, int(round(N ** 0.5)))
    M, B = coded.codes.shape[1], coded.max_bins

    def run(cfg):
        res = gen_dst(jax.random.key(0), coded, cfg=cfg)   # warmup/compile
        jax.block_until_ready(res.fitness)
        t0 = time.perf_counter()
        res = gen_dst(jax.random.key(2), coded, cfg=cfg)
        jax.block_until_ready(res.fitness)
        return (time.perf_counter() - t0) / cfg.psi * 1e6, res

    rows = []
    for mode, cross_every in (("delta", 4), ("full", 1)):
        cfg = GenDSTConfig(psi=psi, phi=phi, cross_every=cross_every)
        us_jnp, r_jnp = run(cfg._replace(backend="jnp"))
        us_fused, r_fused = run(cfg._replace(backend="pallas_fused"))
        assert float(r_jnp.fitness) == float(r_fused.fitness), \
            f"fused backend parity broken ({mode})"
        useful, launched = gen_dst_generation_flops(phi, n, M, B, mode=mode)
        rows.append((f"gen_dst_gen_jnp_{mode}_{quick_tag}", us_jnp,
                     f"loss={-float(r_jnp.fitness):.5f}"))
        rows.append((f"gen_dst_gen_fused_{mode}_{quick_tag}", us_fused,
                     f"useful/launched={useful / launched:.3f}"))
    return rows


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in gen_dst_rows(N=20_000, psi=12, quick_tag="20k"):
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in gen_dst_fused_rows(N=20_000, quick_tag="20k"):
        print(f"{name},{us:.1f},{derived}")
