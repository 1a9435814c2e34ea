"""Batched serving example: prefill + token-by-token decode with a KV cache
(or SSM state), on any assigned architecture's reduced config.

    PYTHONPATH=src python examples/serve_lm.py --arch qwen3-8b
    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-130m --gen 32
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.launch.serve import main as serve_main  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    serve_main(["--arch", args.arch, "--gen", str(args.gen),
                "--temperature", "0.8"])


if __name__ == "__main__":
    enable_compile_cache()
    main()
