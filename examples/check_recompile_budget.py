"""Recompile-budget gate: steady-state serving must not re-trace
(DESIGN.md §15.4).

    PYTHONPATH=src python examples/check_recompile_budget.py
        [--rounds 2] [--jobs 2] [--scale 0.1] [--trials 4]

Round 0 is the warmup: it pays every jit tracing (Gen-DST evolve kernel,
the fused rung evaluator, the promotion mask, full-column entropy).  The
script then snapshots ``obs.jaxprof.tracing_snapshot()`` and replays
``--rounds`` more rounds of *same-shaped* traffic — same datasets, same
plan, fresh PRNG keys, so trial hyperparameters (traced scalars) differ
while every array shape is identical.  PR 6's claim is that shapes, not
values, drive compilation; therefore the steady state must add **zero**
new tracings.  Any nonzero delta prints the offending call sites and
exits 1 — that is a recompile leaked into the serving path.

Plans run with ``fine_tune=False``: the restricted fine-tune pass trains
on the *full* dataset only after a winner family is known, so its first
occurrence may legitimately land in a post-warmup round.  The steady-state
budget is about the per-rung serving path, which SubStrat-NF exercises
fully.  CI runs this as the recompile-budget step.

The same gate also covers the Gen-DST backends directly (DESIGN.md §16):
for every ``GEN_DST_BACKENDS`` entry, one warmup ``gen_dst`` call pays the
tracing, then two same-shaped calls with fresh keys must add zero — the
backend switch is a *static* jit argument, so switching backends between
runs recompiles, but re-running one backend never does.
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

import numpy as np  # noqa: E402

from repro.automl.engine import AutoMLConfig  # noqa: E402
from repro.core.gen_dst import GEN_DST_BACKENDS, GenDSTConfig, gen_dst  # noqa: E402
from repro.core.measures import factorize  # noqa: E402
from repro.core.plan import plan  # noqa: E402
from repro.data.tabular import PAPER_DATASETS, make_dataset, train_test_split  # noqa: E402
from repro.obs import jaxprof  # noqa: E402
from repro.service import SubStratServer  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def run_round(srv, datasets, p, n_jobs, key0):
    ids = []
    for i in range(n_jobs):
        name, Xtr, ytr, Xte, yte = datasets[i % len(datasets)]
        ids.append(srv.submit(Xtr, ytr, tenant="acme",
                              key=jax.random.key(key0 + i), plan=p,
                              X_test=Xte, y_test=yte))
    srv.run()
    for jid in ids:
        st = srv.poll(jid)
        assert st.phase == "done", f"job {jid} ended in {st.phase}"
    return ids


def check_gen_dst_backends(rounds: int) -> int:
    """Warmup + ``rounds`` same-shaped ``gen_dst`` calls per backend: the
    steady state must add 0 jit tracings on every backend, including the
    Pallas legs (interpret mode on CPU — tracing hygiene is backend-blind).
    Returns the number of failing backends."""
    rng = np.random.default_rng(0)
    X = np.column_stack([rng.integers(0, k, 2_000)
                         for k in (3, 5, 17, 2, 40)]).astype(float)
    y = rng.integers(0, 2, 2_000).astype(float)
    coded = factorize(X, y)
    failures = 0
    for backend in GEN_DST_BACKENDS:
        cfg = GenDSTConfig(psi=4, phi=8, cross_every=2, backend=backend)
        res = gen_dst(jax.random.key(0), coded, 20, 3, cfg)   # warmup
        jax.block_until_ready(res.fitness)
        warm = jaxprof.tracing_snapshot()
        for r in range(rounds):
            res = gen_dst(jax.random.key(1 + r), coded, 20, 3, cfg)
            jax.block_until_ready(res.fitness)
        delta = jaxprof.new_tracings_since(warm)
        if delta:
            failures += 1
            print(f"FAIL: gen_dst backend={backend} re-traced after warmup:")
            for site, n in sorted(delta.items()):
                print(f"  {site}: +{int(n)}")
        else:
            print(f"gen_dst backend={backend}: 0 new tracings "
                  f"({rounds} same-shaped rounds, fresh keys)")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=2,
                    help="steady-state rounds replayed after the warmup")
    ap.add_argument("--jobs", type=int, default=2,
                    help="jobs per round (constant so megabatch group "
                         "sizes match between warmup and steady state)")
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--trials", type=int, default=4)
    args = ap.parse_args()

    datasets = []
    for name in ("D3", "D6")[:max(1, min(2, args.jobs))]:
        X, y = make_dataset(PAPER_DATASETS[name], scale=args.scale)
        Xtr, ytr, Xte, yte = train_test_split(X, y)
        datasets.append((name, Xtr, ytr, Xte, yte))

    p = plan("gen_dst", cfg=GenDSTConfig(psi=8, phi=20), fine_tune=False,
             sub_automl=AutoMLConfig(n_trials=args.trials, rungs=(30, 80)))

    srv = SubStratServer()
    run_round(srv, datasets, p, args.jobs, key0=0)
    warm = jaxprof.tracing_snapshot()
    print(f"warmup: {int(sum(warm.values()))} jit tracings across "
          f"{len(warm)} call sites")
    for site, n in sorted(warm.items()):
        print(f"  {site}: {int(n)}")

    for r in range(args.rounds):
        run_round(srv, datasets, p, args.jobs, key0=100 * (r + 1))
        delta = jaxprof.new_tracings_since(warm)
        if delta:
            print(f"FAIL: round {r + 1} re-traced after warmup:")
            for site, n in sorted(delta.items()):
                print(f"  {site}: +{int(n)}")
            return 1
        print(f"round {r + 1}: 0 new tracings "
              f"({args.jobs} jobs, fresh keys, same shapes)")

    if check_gen_dst_backends(args.rounds):
        return 1

    print("recompile budget: PASS (steady state adds 0 jit tracings)")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
