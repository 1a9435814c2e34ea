"""Quickstart: SubStrat vs Full-AutoML on a paper-shaped tabular dataset.

    PYTHONPATH=src python examples/quickstart.py [--scale 0.5] [--trials 10]
                                                 [--backend batched|loop]
                                                 [--strategy gen_dst|mc|...]

Reproduces the paper's headline comparison on one dataset: run the AutoML
engine on the full data, then execute a SubStrat ``Plan`` (subset strategy
-> AutoML -> restricted fine-tune) and report time-reduction + relative
accuracy.  ``--scale 0.1 --trials 4`` is the CI smoke configuration;
``--backend loop`` pins the sequential AutoML reference engine (DESIGN.md
§10.3); ``--strategy`` swaps the subset finder across the SubsetStrategy
registry (DESIGN.md §12.1) — the paper's Gen-DST by default.
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

from repro.automl.engine import AutoMLConfig, automl_fit  # noqa: E402
from repro.core.gen_dst import GenDSTConfig  # noqa: E402
from repro.core.plan import execute, plan  # noqa: E402
from repro.core.strategies import available_strategies  # noqa: E402
from repro.data.tabular import PAPER_DATASETS, make_dataset, train_test_split  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.5,
                    help="dataset row-count scale (0.1 = smoke size)")
    ap.add_argument("--trials", type=int, default=10,
                    help="AutoML trial budget for the full and sub passes")
    ap.add_argument("--backend", default="batched", choices=("batched", "loop"),
                    help="AutoML engine backend (DESIGN.md §10.3)")
    ap.add_argument("--strategy", default="gen_dst",
                    choices=available_strategies(),
                    help="SubsetStrategy registry entry (DESIGN.md §12.1)")
    args = ap.parse_args()

    spec = PAPER_DATASETS["D3"]           # car insurance, 10k x 18
    X, y = make_dataset(spec, scale=args.scale)
    Xtr, ytr, Xte, yte = train_test_split(X, y)
    print(f"dataset {spec.name} ({spec.domain}): {Xtr.shape[0]} train rows, "
          f"{Xtr.shape[1]} columns, engine backend {args.backend}, "
          f"subset strategy {args.strategy}")

    automl_cfg = AutoMLConfig(n_trials=args.trials, rungs=(60, 200),
                              backend=args.backend)
    t0 = time.perf_counter()
    full = automl_fit(Xtr, ytr, config=automl_cfg, X_test=Xte, y_test=yte)
    t_full = time.perf_counter() - t0
    print(f"\nFull-AutoML : {t_full:6.1f}s  test-acc {full.test_acc:.3f} "
          f"({full.spec.family}, {full.n_trials} trials)")

    opts = {"cfg": GenDSTConfig(psi=10, phi=24)} \
        if args.strategy in ("gen_dst", "gen_dst_islands") else {}
    p = plan(
        args.strategy,
        sub_automl=automl_cfg,
        ft_automl=AutoMLConfig(n_trials=4, rungs=(120,), backend=args.backend),
        **opts,
    )
    res = execute(p, Xtr, ytr, key=jax.random.key(0), X_test=Xte, y_test=yte)
    print(f"SubStrat    : {res.total_time_s:6.1f}s  test-acc "
          f"{res.final.test_acc:.3f} ({res.final.spec.family})")
    print(f"  subset: {len(res.row_idx)} rows x {len(res.col_idx)}(+target) cols, "
          f"|H(d)-H(D)| = {-res.dst_fitness:.4f}")
    print(f"  phases: {', '.join(f'{k}={v:.1f}s' for k, v in res.times.items())}")
    print(f"\ntime-reduction     = {1 - res.total_time_s / t_full:+.1%}")
    print(f"relative-accuracy  = {res.final.test_acc / full.test_acc:.1%}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
