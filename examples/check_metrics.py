"""Metrics smoke gate: scrape ``GET /v1/metrics`` during a real serve run
and validate the exposition (DESIGN.md §15.3).

    PYTHONPATH=src python examples/check_metrics.py [--jobs 2] [--scale 0.1]
                                                    [--trials 4]

Stands up the HTTP front end over an in-process scheduler, submits a pair
of jobs (the second is a DST-cache repeat of the first), waits for both
over ``/v1/result``, then scrapes ``/v1/metrics`` and fails (exit 1) unless

- every non-comment line parses as a Prometheus 0.0.4 sample,
- every sample's family carries ``# TYPE``/``# HELP`` headers,
- the dispatch counters are nonzero (``dispatches_total`` summed over its
  ``mode`` children >= 1, and ``dispatch_latency_seconds_count`` agrees),
- the DST cache saw the repeat (``cache_hits_total >= 1``), and
- jit-tracing accounting is live (``jax_jit_tracings_total`` > 0 — a cold
  process must have compiled *something* to finish a job).

CI runs this as the metrics-smoke step.
"""
import argparse
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

from repro.automl.engine import AutoMLConfig  # noqa: E402
from repro.core.gen_dst import GenDSTConfig  # noqa: E402
from repro.core.plan import plan  # noqa: E402
from repro.data.tabular import PAPER_DATASETS, make_dataset, train_test_split  # noqa: E402
from repro.service import (  # noqa: E402
    SubStratHTTPClient, SubStratHTTPServer, SubStratServer,
)
from repro.compile_cache import enable_compile_cache  # noqa: E402

# sample line: name{label="v",...} value  — value may be int/float/+Inf
_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (-?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?|\+Inf|-Inf|NaN))$')


def parse_exposition(text: str):
    """Validate the text format; returns {family: summed value} and the
    set of families that carried TYPE headers.  Raises ValueError with the
    offending line on any malformed input."""
    typed, helped, sums = set(), set(), {}
    for lineno, line in enumerate(text.splitlines(), start=1):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            if len(parts) != 4 or parts[3] not in (
                    "counter", "gauge", "histogram", "summary", "untyped"):
                raise ValueError(f"line {lineno}: malformed TYPE: {line!r}")
            typed.add(parts[2])
            continue
        if line.startswith("# HELP "):
            if len(line.split(" ", 3)) < 3:
                raise ValueError(f"line {lineno}: malformed HELP: {line!r}")
            helped.add(line.split(" ", 3)[2])
            continue
        if line.startswith("#"):
            continue   # free-form comment — legal
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"line {lineno}: malformed sample: {line!r}")
        name, value = m.group(1), m.group(3)
        # histogram series sample under the family's TYPE header
        family = re.sub(r"_(bucket|sum|count)$", "", name)
        if name not in typed and family not in typed:
            raise ValueError(
                f"line {lineno}: sample {name!r} precedes its TYPE header")
        if value not in ("+Inf", "-Inf", "NaN"):
            sums[name] = sums.get(name, 0.0) + float(value)
    return sums, typed


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--trials", type=int, default=4)
    args = ap.parse_args()

    X, y = make_dataset(PAPER_DATASETS["D3"], scale=args.scale)
    Xtr, ytr, Xte, yte = train_test_split(X, y)
    p = plan("gen_dst", cfg=GenDSTConfig(psi=8, phi=20),
             sub_automl=AutoMLConfig(n_trials=args.trials, rungs=(30, 80)),
             ft_automl=AutoMLConfig(n_trials=4, rungs=(80,)))

    http = SubStratHTTPServer(SubStratServer()).start()
    failures = []
    try:
        client = SubStratHTTPClient(http.url)
        ids = [client.submit(Xtr, ytr, tenant="acme", key=jax.random.key(i),
                             plan=p, X_test=Xte, y_test=yte)
               for i in range(args.jobs)]
        for jid in ids:
            client.result(jid)

        text = client.metrics()
        print(f"scraped {len(text.splitlines())} exposition lines "
              f"from {http.url}/v1/metrics")
        try:
            sums, typed = parse_exposition(text)
        except ValueError as e:
            print(f"FAIL: {e}")
            return 1

        def check(cond, what):
            print(("ok:   " if cond else "FAIL: ") + what)
            if not cond:
                failures.append(what)

        dispatches = sum(v for n, v in sums.items()
                         if n == "dispatches_total")
        check(dispatches >= 1,
              f"dispatches_total summed over modes >= 1 (got {dispatches})")
        check(sums.get("dispatch_latency_seconds_count", 0.0) == dispatches,
              "dispatch_latency_seconds_count agrees with dispatches_total")
        check(sums.get("cache_hits_total", 0.0) >= 1,
              "cache_hits_total >= 1 (job 1 repeats job 0's dataset)")
        check(sums.get("jobs_finished_total", 0.0) == len(ids),
              f"jobs_finished_total == {len(ids)}")
        check("jax_jit_tracings_total" in typed
              and sums.get("jax_jit_tracings_total", 0.0) > 0,
              "jax_jit_tracings_total present and nonzero")
    finally:
        http.close()
        if hasattr(http.server.scheduler, "close"):
            http.server.scheduler.close()

    print(f"metrics smoke: {len(failures)} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
