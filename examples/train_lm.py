"""End-to-end LM training driver with SubStrat corpus selection.

    PYTHONPATH=src python examples/train_lm.py                  # CPU-sized
    PYTHONPATH=src python examples/train_lm.py --preset full    # ~130M mamba2

Trains the mamba2-130m architecture (reduced on CPU) for a few hundred
steps, comparing a run on the full synthetic corpus against a run on a
Gen-DST entropy-preserving subset (SubStrat step 1 at LM scale), with
checkpoint/restart enabled.
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.launch.train import main as train_main  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["cpu-small", "full"], default="cpu-small")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="mamba2-130m")
    args = ap.parse_args()

    common = ["--arch", args.arch, "--preset", args.preset,
              "--steps", str(args.steps), "--batch", "8", "--seq", "128"]

    print("=== run A: full corpus ===")
    train_main(common + ["--ckpt-dir", "checkpoints/full"])

    print("\n=== run B: SubStrat-selected corpus subset (step 1 of the paper "
          "strategy at LM scale) ===")
    train_main(common + ["--substrat-subset", "256",
                         "--ckpt-dir", "checkpoints/substrat"])


if __name__ == "__main__":
    enable_compile_cache()
    main()
