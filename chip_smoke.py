"""Chip smoke test: the served SubStrat path on one TPU at paper-table scale.

    python chip_smoke.py

Everything runs in this one process, because the process that touches JAX
owns the chip.  The script exits non-zero, and prints no result, unless
JAX's default device is a TPU.  Phases:

1. **device** — the devices JAX sees.
2. **kernels** — both Pallas kernels, compiled, against their jnp oracles at
   the padding edges: the histogram bit for bit, the fused generation's
   counts bit for bit and its fitness within 1e-5.
3. **gen_dst** — the paper's D1 table at full scale (129,880 x 22),
   factorized, searched by Gen-DST under the default ``GenDSTConfig`` with
   one key on each backend: ``jnp``, ``pallas`` and ``pallas_fused``.  The
   Pallas kernels must run compiled, and each Pallas best fitness must lie
   within 1e-5 of the ``jnp`` one.
4. **serve** — an in-process ``SubStratServer`` behind ``SubStratHTTPServer``
   on localhost.  Through ``SubStratHTTPClient`` it takes three jobs under
   the default ``plan("gen_dst")``: D1, the same D1 table again (a DST-cache
   hit), and D10 (1,000,000 x 14).  Each must finish with a test accuracy
   above its majority-class rate.
5. **parity** — the first job against a one-shot ``execute()`` of the same
   table and key: equal winner specs, trial accuracies within 1e-6.

Each phase prints its wall time, and beside it the seconds JAX spent in XLA
compilation (or loading from the persistent cache) within it.  The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

D10_SCALE = 1.0          # rows of D10 kept (1.0 = all 1,000,000)
FITNESS_ATOL = 1e-5      # Pallas vs jnp Gen-DST best fitness
TRIAL_ACC_ATOL = 1e-6    # served vs one-shot trial accuracies
JOB_TIMEOUT_S = 1100.0

# (N, M, B, codes below): a lone row tile, a ragged column tile, bins no
# code reaches, one past an (8, 128) tile, and D1's population fold
HISTOGRAM_EDGES = ((5, 3, 8, 8), (300, 13, 16, 16), (200, 4, 64, 11),
                   (9, 129, 129, 129), (360, 2300, 256, 256))
# (P, M, B): D1's population, and a ragged candidate tile at D8's width
FUSED_EDGES = ((100, 23, 256), (13, 122, 256))

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


class CompileClock:
    """Seconds JAX spends in XLA compilation (or loading a compiled program
    from the persistent cache), and the cache's hits, over every thread."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1


class Phase:
    """Times one phase: wall seconds and the compile seconds within them."""

    def __init__(self, clock: CompileClock, name: str):
        self.clock, self.name = clock, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.clock.seconds, self.clock.cache_hits
        return self

    def __exit__(self, *exc):
        wall_s = time.perf_counter() - self.t0
        compile_s = self.clock.seconds - self.c0
        if exc[0] is None:
            print(f"[time] {self.name}: wall {wall_s:.3f} s = compile "
                  f"{compile_s:.3f} s + other {wall_s - compile_s:.3f} s "
                  f"({self.clock.cache_hits - self.h0} persistent-cache "
                  f"hits)", flush=True)
        return False


def check_device(jax) -> dict:
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] {devices}", flush=True)
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX's default device is "
                         f"{dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def kernel_parity(jax, clock) -> None:
    """Both kernels, compiled, against their jnp oracles at padding edges."""
    import jax.numpy as jnp
    from repro.kernels.entropy.kernel import masked_histogram_pallas
    from repro.kernels.entropy.ref import masked_histogram_ref
    from repro.kernels.gen_dst.kernel import fused_delta_fitness_pallas
    from repro.kernels.gen_dst.ref import fused_delta_fitness_ref

    rng = np.random.default_rng(0)
    with Phase(clock, "kernel parity (compiled vs jnp oracle)"):
        for N, M, B, hi in HISTOGRAM_EDGES:
            codes = jnp.asarray(rng.integers(0, hi, (N, M)), jnp.int32)
            w = jnp.asarray((rng.random(N) < 0.6).astype(np.float32))
            got = np.asarray(masked_histogram_pallas(codes, w, B))
            want = np.asarray(masked_histogram_ref(codes, w, B))
            _require(np.array_equal(got, want),
                     f"histogram kernel differs from its oracle at "
                     f"{(N, M, B)}")
        for P, M, B in FUSED_EDGES:
            base = rng.integers(0, B, (P, 12, M))
            counts = np.zeros((P, M, B), np.float32)
            np.add.at(counts, (np.arange(P)[:, None, None],
                               np.arange(M)[None, None, :], base), 1.0)
            cols = rng.random((P, M)) < 0.5
            cols[:, 0] = True
            args = (jnp.asarray(counts), jnp.asarray(base[:, 0, :], jnp.int32),
                    jnp.asarray(rng.integers(0, B, (P, M)), jnp.int32),
                    jnp.asarray(rng.random(P) < 0.6), jnp.asarray(cols),
                    jnp.float32(2.0))
            want_c, want_f = fused_delta_fitness_ref(*args)
            got_c, got_f = fused_delta_fitness_pallas(*args, bins=B)
            dfit = float(np.max(np.abs(np.asarray(got_f)
                                       - np.asarray(want_f))))
            print(f"[kernels] fused {(P, M, B)}: counts equal "
                  f"{np.array_equal(np.asarray(got_c), np.asarray(want_c))}"
                  f", max |dfitness| {dfit:.3g}", flush=True)
            _require(np.array_equal(np.asarray(got_c), np.asarray(want_c)),
                     f"fused kernel counts differ at {(P, M, B)}")
            _require(dfit <= FITNESS_ATOL,
                     f"fused kernel fitness differs at {(P, M, B)}")
    print(f"[kernels] histogram equal to its oracle bit for bit at "
          f"{len(HISTOGRAM_EDGES)} shapes", flush=True)


def gen_dst_backends(jax, clock) -> None:
    from repro.core.gen_dst import GenDSTConfig, gen_dst
    from repro.core.measures import factorize
    from repro.data.tabular import PAPER_DATASETS, make_dataset
    from repro.kernels import resolve_interpret

    _require(resolve_interpret(None) is False,
             "Pallas kernels would run interpreted on this backend")
    X, y = make_dataset(PAPER_DATASETS["D1"], scale=1.0)
    with Phase(clock, "gen_dst factorize D1"):
        coded = factorize(X, y)
    print(f"[gen_dst] D1 codes {coded.codes.shape}, B={coded.max_bins}",
          flush=True)
    key = jax.random.key(0)
    results = {}
    for backend in ("jnp", "pallas", "pallas_fused"):
        cfg = GenDSTConfig(backend=backend)
        with Phase(clock, f"gen_dst[{backend}] first call"):
            res = jax.block_until_ready(gen_dst(key, coded, cfg=cfg))
        with Phase(clock, f"gen_dst[{backend}] warm call"):
            jax.block_until_ready(gen_dst(key, coded, cfg=cfg))
        results[backend] = res
        print(f"[gen_dst] {backend}: best fitness {float(res.fitness):.9g}, "
              f"n={res.row_idx.shape[0]}, m={int(res.col_mask.sum())}",
              flush=True)
    ref = results["jnp"]
    for backend in ("pallas", "pallas_fused"):
        res = results[backend]
        dfit = abs(float(res.fitness) - float(ref.fitness))
        same_rows = np.array_equal(np.asarray(res.row_idx),
                                   np.asarray(ref.row_idx))
        same_cols = np.array_equal(np.asarray(res.col_mask),
                                   np.asarray(ref.col_mask))
        print(f"[gen_dst] {backend} vs jnp: |dfitness| {dfit:.3g}, "
              f"same rows {same_rows}, same columns {same_cols}", flush=True)
        _require(dfit <= FITNESS_ATOL,
                 f"{backend} best fitness is {dfit} from jnp's")


def serve_and_compare(jax, clock) -> None:
    from repro.core.plan import execute, plan
    from repro.data.tabular import (PAPER_DATASETS, make_dataset,
                                    train_test_split)
    from repro.service import (SubStratHTTPClient, SubStratHTTPServer,
                               SubStratServer)

    tables = {}
    for name, scale in (("D1", 1.0), ("D10", D10_SCALE)):
        X, y = make_dataset(PAPER_DATASETS[name], scale=scale)
        tables[name] = train_test_split(X, y)
        print(f"[serve] {name} at scale {scale}: {X.shape[0]} x "
              f"{X.shape[1]}", flush=True)
    p = plan("gen_dst")
    jobs = (("D1", 0), ("D1", 0), ("D10", 1))   # (table, key seed)

    http = SubStratHTTPServer(SubStratServer()).start()
    try:
        client = SubStratHTTPClient(http.url, timeout_s=JOB_TIMEOUT_S)
        with Phase(clock, "serve 3 jobs (submit to last result)"):
            ids = []
            for name, seed in jobs:
                Xtr, ytr, Xte, yte = tables[name]
                ids.append(client.submit(
                    Xtr, ytr, tenant="smoke", key=jax.random.key(seed),
                    plan=p, X_test=Xte, y_test=yte))
            served = [client.result(jid, timeout_s=JOB_TIMEOUT_S)
                      for jid in ids]
        for jid, (name, _), res in zip(ids, jobs, served):
            st = client.poll(jid)
            yte = tables[name][3]
            majority = np.bincount(yte).max() / len(yte)
            times = " ".join(f"{k}={v:.3f}s"
                             for k, v in st["phase_times"].items())
            print(f"[serve] job {jid} {name}: phase {st['phase']}, "
                  f"cache_hit {st['cache_hit']}, warm_started "
                  f"{st['warm_started']}, winner {res.final.spec.family}/"
                  f"{res.final.spec.preproc}, test acc "
                  f"{res.final.test_acc:.4f} (majority {majority:.4f}); "
                  f"{times}", flush=True)
            _require(st["phase"] == "done", f"job {jid} ended in {st['phase']}")
            _require(res.final.test_acc > majority,
                     f"job {jid} test accuracy is not above the majority rate")
        _require(client.poll(ids[1])["cache_hit"],
                 "the D1 repeat missed the DST cache")
    finally:
        http.close()

    Xtr, ytr, Xte, yte = tables["D1"]
    with Phase(clock, "one-shot execute D1"):
        one = execute(p, Xtr, ytr, key=jax.random.key(0),
                      X_test=Xte, y_test=yte)
    got = served[0]
    for label, a, b in (("sub-automl", got.intermediate, one.intermediate),
                        ("fine-tune", got.final, one.final)):
        _require(a.spec == b.spec, f"{label} winner {a.spec} != {b.spec}")
        _require([s for s, _ in a.trials] == [s for s, _ in b.trials],
                 f"{label} trial specs differ")
        dacc = float(np.max(np.abs(np.array([v for _, v in a.trials])
                                   - np.array([v for _, v in b.trials]))))
        print(f"[parity] {label}: winner {a.spec.family}/{a.spec.preproc} "
              f"equal, max |dacc| {dacc:.3g} over {len(a.trials)} trials",
              flush=True)
        _require(dacc <= TRIAL_ACC_ATOL, f"{label} trial accuracies differ")


def main() -> int:
    import jax

    device = check_device(jax)
    from repro.compile_cache import enable_compile_cache
    print(f"[cache] compilation cache at {enable_compile_cache()}",
          flush=True)
    clock = CompileClock(jax)
    with Phase(clock, "all phases"):
        kernel_parity(jax, clock)
        gen_dst_backends(jax, clock)
        serve_and_compare(jax, clock)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
