"""Baseline comparison driver (paper Table 4, one dataset): SubStrat vs the
baseline DST generators vs Full-AutoML.

    PYTHONPATH=src python examples/automl_tabular.py --dataset D6 --scale 0.2 \
        [--backend batched|loop]

``--backend`` switches every AutoML pass (full, sub, fine-tune) between the
batched vmap engine and the sequential reference (DESIGN.md §10.3).
"""
import argparse
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import QUICK_AUTOML, run_dataset, substrat_config  # noqa: E402
from repro.data.tabular import PAPER_DATASETS  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="D6", choices=sorted(PAPER_DATASETS))
    ap.add_argument("--scale", type=float, default=0.2)
    ap.add_argument("--methods", nargs="*", default=None)
    ap.add_argument("--backend", default="batched", choices=("batched", "loop"))
    args = ap.parse_args()

    full, results = run_dataset(
        PAPER_DATASETS[args.dataset], scale=args.scale, methods=args.methods,
        full_cfg=dataclasses.replace(QUICK_AUTOML, backend=args.backend),
        sub_cfg=substrat_config(automl_backend=args.backend),
    )
    print(f"\n{args.dataset}: Full-AutoML {full.time_s:.1f}s, "
          f"test-acc {full.test_acc:.3f}\n")
    print(f"{'method':14s} {'time':>8s} {'time-red':>9s} {'acc':>6s} {'rel-acc':>8s}")
    for r in sorted(results, key=lambda r: -r.relative_accuracy):
        print(f"{r.method:14s} {r.time_s:7.1f}s {r.time_reduction:+8.1%} "
              f"{r.test_acc:6.3f} {r.relative_accuracy:7.1%}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
