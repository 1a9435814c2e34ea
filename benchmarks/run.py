"""Benchmark orchestrator — one section per paper table/figure + kernel
micro-benches + the service-layer / hetero-merge benches + the dry-run
roofline table.

    PYTHONPATH=src python -m benchmarks.run [--full] [--json PATH]
                                            [--compare BASELINE.json]

Prints ``name,us_per_call,derived`` CSV blocks per section.  --full uses the
paper-scale settings (long); the default quick mode scales datasets down so
the whole suite finishes on one CPU core.  --json additionally writes every
section's rows to a machine-readable file so the perf trajectory can be
tracked across PRs (CI uploads it as ``BENCH_quick.json``) instead of
scraping CSV from stdout.  The report carries a top-level ``meta`` block
(jax/jaxlib version, device kind, CPU count, timestamp) so artifacts from
different machines are attributable.  --compare reads a previous run's
--json artifact — either layout, with or without ``meta`` —
and exits non-zero when any section regressed by more than
--compare-threshold (default 15%) in wall seconds — CI runs it against the
committed ``benchmarks/BASELINE_quick.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def _section(title):
    print(f"\n### {title}", flush=True)


def _run_meta() -> dict:
    """Run context stamped into the --json report so BENCH_*.json
    trajectories are comparable across machines/toolchains."""
    import datetime
    import os

    meta = {
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
                     .isoformat(timespec="seconds"),
        "cpu_count": os.cpu_count(),
        "platform": sys.platform,
    }
    try:
        import jax
        import jaxlib
        meta["jax_version"] = jax.__version__
        meta["jaxlib_version"] = jaxlib.__version__
        meta["device_kind"] = jax.devices()[0].device_kind
        meta["device_count"] = jax.device_count()
    except Exception:  # noqa: BLE001 — meta is best-effort context
        meta.setdefault("jax_version", None)
    return meta


def _rowdicts(columns, rows):
    """JSON payload of one section: a list of {column: value} dicts."""
    return [dict(zip(columns, row)) for row in rows]


def _compare(report: dict, baseline_path: str, threshold: float) -> int:
    """Compare per-section wall seconds against a previous --json artifact.

    Returns the number of regressed sections (> ``threshold`` slower).
    Sections missing from either side are reported but never fail — a new
    section has no baseline, a removed one no measurement."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    base_secs = {name: sec["seconds"]
                 for name, sec in baseline.get("sections", {}).items()
                 if not sec.get("failed")}
    print(f"\n### comparison vs {baseline_path} "
          f"(threshold {threshold:.0%})")
    print("section,baseline_s,current_s,ratio,verdict")
    regressions = 0
    for name, sec in report["sections"].items():
        if sec["failed"]:
            continue
        if name not in base_secs:
            print(f"{name},-,{sec['seconds']:.3f},-,new (no baseline)")
            continue
        base = base_secs.pop(name)
        cur = sec["seconds"]
        ratio = cur / max(base, 1e-9)
        regressed = ratio > 1.0 + threshold
        regressions += int(regressed)
        print(f"{name},{base:.3f},{cur:.3f},{ratio:.2f}x,"
              f"{'REGRESSED' if regressed else 'ok'}")
    for name in base_secs:
        print(f"{name},{base_secs[name]:.3f},-,-,missing from this run")
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip", nargs="*", default=[],
                    help="section names to skip (table4 fig2 fig3 fig4 fig5 "
                         "kernels gen_dst automl service service_transport "
                         "hetero_merge continuous_batching meta roofline)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write each section's rows to a machine-readable "
                         "JSON file (perf trajectory tracking across PRs)")
    ap.add_argument("--compare", metavar="BASELINE", default=None,
                    help="previous --json artifact to compare against; "
                         "exits 2 when any section regresses by more than "
                         "--compare-threshold in wall seconds")
    ap.add_argument("--compare-threshold", type=float, default=0.15,
                    help="allowed per-section slowdown fraction "
                         "(default 0.15 = 15%%)")
    ap.add_argument("--compare-only", metavar="CURRENT", default=None,
                    help="skip running benchmarks; compare an existing "
                         "--json artifact against --compare's baseline")
    args = ap.parse_args()

    if args.compare_only:
        if not args.compare:
            ap.error("--compare-only requires --compare BASELINE.json")
        with open(args.compare_only) as f:
            report = json.load(f)
        regressions = _compare(report, args.compare, args.compare_threshold)
        print(f"# {regressions} section regressions "
              f"(>{args.compare_threshold:.0%} slower)")
        sys.exit(2 if regressions else 0)

    quick = not args.full
    t_start = time.time()

    sections = []

    if "kernels" not in args.skip:
        sections.append(("kernels", _run_kernels))
    if "gen_dst" not in args.skip:
        sections.append(("gen_dst", lambda: _run_gen_dst(quick)))
    if "automl" not in args.skip:
        sections.append(("automl", lambda: _run_automl(quick)))
    if "service" not in args.skip:
        sections.append(("service", lambda: _run_service(quick)))
    if "service_transport" not in args.skip:
        sections.append(("service_transport", lambda: _run_transport(quick)))
    if "hetero_merge" not in args.skip:
        sections.append(("hetero_merge", lambda: _run_hetero(quick)))
    if "continuous_batching" not in args.skip:
        sections.append(("continuous_batching", lambda: _run_continuous(quick)))
    if "meta" not in args.skip:
        sections.append(("meta", lambda: _run_meta_learning(quick)))
    if "table4" not in args.skip:
        sections.append(("table4", lambda: _run_table4(quick)))
    if "fig2" not in args.skip:
        sections.append(("fig2", lambda: _run_fig2(quick)))
    if "fig3" not in args.skip:
        sections.append(("fig3", lambda: _run_fig3(quick)))
    if "fig4" not in args.skip:
        sections.append(("fig4", lambda: _run_fig4(quick)))
    if "fig5" not in args.skip:
        sections.append(("fig5", lambda: _run_fig5(quick)))
    if "roofline" not in args.skip:
        sections.append(("roofline", _run_roofline))

    report = {"quick": quick, "meta": _run_meta(), "sections": {}}
    failures = 0
    for name, fn in sections:
        t0 = time.time()
        try:
            rows = fn()
        except Exception:  # noqa: BLE001 — keep the suite running
            failures += 1
            rows = None
            print(f"SECTION {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
        dt = time.time() - t0
        report["sections"][name] = {
            "seconds": round(dt, 3),
            "failed": rows is None,
            "rows": rows if rows is not None else [],
        }
        print(f"# section {name} took {dt:.1f}s", flush=True)
    report["failures"] = failures
    report["total_s"] = round(time.time() - t_start, 3)
    print(f"\n# benchmarks done in {report['total_s']:.1f}s, "
          f"{failures} section failures")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, default=float)
        print(f"# wrote {args.json}")
    regressions = 0
    if args.compare:
        regressions = _compare(report, args.compare, args.compare_threshold)
        print(f"# {regressions} section regressions "
              f"(>{args.compare_threshold:.0%} slower)")
    if failures:
        sys.exit(1)
    if regressions:
        sys.exit(2)


def _run_kernels():
    _section("kernel micro-benchmarks (name,us_per_call,derived)")
    from .kernels_bench import main as kmain
    rows = [(name, round(us, 1), derived) for name, us, derived in kmain()]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return _rowdicts(("name", "us_per_call", "derived"), rows)


def _run_gen_dst(quick):
    _section("Gen-DST search loop: incremental fitness + islands "
             "(name,us_per_generation,derived)")
    from .kernels_bench import gen_dst_fused_rows, gen_dst_rows
    if quick:
        rows = gen_dst_rows(N=20_000, psi=12, quick_tag="20k")
        rows += gen_dst_fused_rows(N=20_000, psi=6, phi=16, quick_tag="20k")
    else:
        rows = gen_dst_rows(N=100_000, psi=24, quick_tag="100k")
        rows += gen_dst_fused_rows(N=100_000, psi=12, phi=64,
                                   quick_tag="100k")
    rows = [(name, round(us, 1), derived) for name, us, derived in rows]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return _rowdicts(("name", "us_per_generation", "derived"), rows)


def _run_automl(quick):
    _section("AutoML engine: sequential loop vs batched cohort, default "
             "24-trial/3-rung budget (name,us,derived)")
    from .automl_bench import automl_rows
    # dst100 = the sub-AutoML regime (DST of quickstart's 10k-row dataset);
    # the larger dataset shows the compute-bound end of the scale
    rows = automl_rows(N=100, d=12, quick_tag="dst100")
    rows += automl_rows(N=2_000 if quick else 10_000, d=12,
                        quick_tag="2k" if quick else "10k", reps=2)
    rows = [(name, round(us, 1), derived) for name, us, derived in rows]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return _rowdicts(("name", "us", "derived"), rows)


def _run_service(quick):
    _section("Service layer: 8 concurrent jobs (DST cache + warm start + "
             "cross-job rung merge) vs sequential substrat (name,us,derived)")
    from .service_bench import service_rows
    if quick:
        rows = service_rows(n_jobs=8, N=2_000, d=10, quick_tag="2k")
    else:
        rows = service_rows(n_jobs=8, N=10_000, d=14, quick_tag="10k")
    rows = [(name, round(us, 1), derived) for name, us, derived in rows]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return _rowdicts(("name", "us", "derived"), rows)


def _run_transport(quick):
    _section("Cross-process serving tier: in-process vs 1 vs 2 worker "
             "subprocesses + crash recovery overhead (name,us,derived)")
    from .transport_bench import transport_rows
    rows = transport_rows(n_jobs=4, N=512 if quick else 2_000,
                          quick_tag="quick" if quick else "full")
    rows = [(name, round(us, 1), derived) for name, us, derived in rows]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return _rowdicts(("name", "us", "derived"), rows)


def _run_hetero(quick):
    _section("Heterogeneous merge: shape-padded cross-job rung dispatch + "
             "batched Gen-DST (name,us,derived)")
    from .hetero_bench import hetero_rows
    rows = hetero_rows(n_jobs=4, quick_tag="quick" if quick else "full")
    rows = [(name, round(us, 1), derived) for name, us, derived in rows]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return _rowdicts(("name", "us", "derived"), rows)


def _run_continuous(quick):
    _section("Continuous rung batching: lockstep (rung_i, epochs) buckets vs "
             "cross-rung step-masked megabatch (name,us,derived)")
    from .continuous_bench import continuous_rows
    rows = continuous_rows(n_jobs=8, quick_tag="quick" if quick else "full")
    rows = [(name, round(us, 1), derived) for name, us, derived in rows]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return _rowdicts(("name", "us", "derived"), rows)


def _run_meta_learning(quick):
    _section("Cross-tenant meta-learning: trials to reach the cold winner "
             "accuracy, cold vs portfolio-warm-started (name,us,derived)")
    from .meta_bench import meta_rows
    if quick:
        rows = meta_rows(n_history=4, n_eval=8, N=400, d=8,
                         quick_tag="quick")
    else:
        rows = meta_rows(n_history=8, n_eval=8, N=2_000, d=10,
                         quick_tag="full")
    rows = [(name, round(us, 1), derived) for name, us, derived in rows]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return _rowdicts(("name", "us", "derived"), rows)


def _run_table4(quick):
    _section("Table 4: mean time-reduction / relative-accuracy per method")
    from .table4_baselines import main as t4
    datasets = ("D2", "D3", "D6") if quick else tuple(f"D{i}" for i in range(1, 11))
    table = t4(datasets=datasets, scale=0.2 if quick else 1.0,
               reps=1 if quick else 5, print_rows=False)
    print("method,time_reduction_mean,time_reduction_std,rel_acc_mean,rel_acc_std")
    rows = []
    for m, (trm, trs, ram, ras) in sorted(table.items(), key=lambda kv: -kv[1][2]):
        print(f"{m},{trm:.4f},{trs:.4f},{ram:.4f},{ras:.4f}")
        rows.append((m, trm, trs, ram, ras))
    return _rowdicts(("method", "time_reduction_mean", "time_reduction_std",
                      "rel_acc_mean", "rel_acc_std"), rows)


def _run_fig2(quick):
    _section("Figure 2: per-dataset points")
    from .fig2_per_dataset import main as f2
    print("dataset,method,time_reduction,relative_accuracy")
    rows = list(f2(scale=0.2 if quick else 1.0))
    for ds, m, tr, ra in rows:
        print(f"{ds},{m},{tr:.4f},{ra:.4f}")
    return _rowdicts(("dataset", "method", "time_reduction",
                      "relative_accuracy"), rows)


def _run_fig3(quick):
    _section("Figure 3: SubStrat settings skyline")
    from .fig3_skyline import main as f3
    points, skyline = f3(scale=0.2 if quick else 1.0)
    sky = {p[0] for p in skyline}
    print("setting,time_reduction,relative_accuracy,on_skyline")
    rows = []
    for name, tr, ra in points:
        print(f"{name},{tr:.4f},{ra:.4f},{name in sky}")
        rows.append((name, tr, ra, name in sky))
    return _rowdicts(("setting", "time_reduction", "relative_accuracy",
                      "on_skyline"), rows)


def _run_fig4(quick):
    _section("Figure 4: DST size heatmap")
    from .fig4_dst_size import main as f4
    print("n,m,time_reduction,relative_accuracy")
    rows = list(f4(scale=0.15 if quick else 1.0))
    for n, m, tr, ra in rows:
        print(f"{n},{m},{tr:.4f},{ra:.4f}")
    return _rowdicts(("n", "m", "time_reduction", "relative_accuracy"), rows)


def _run_fig5(quick):
    _section("Figure 5: isolated n / m sweeps")
    from .fig5_isolated import main as f5
    lp, wp = f5(scale=0.15 if quick else 1.0)
    print("axis,value,time_reduction,relative_accuracy")
    rows = []
    for n, tr, ra in lp:
        print(f"n,{n},{tr:.4f},{ra:.4f}")
        rows.append(("n", n, tr, ra))
    for m, tr, ra in wp:
        print(f"m,{m},{tr:.4f},{ra:.4f}")
        rows.append(("m", m, tr, ra))
    return _rowdicts(("axis", "value", "time_reduction",
                      "relative_accuracy"), rows)


def _run_roofline():
    _section("Roofline (experiments/dryrun.json + analytic Gen-DST fused "
             "generation)")
    from .roofline import gen_dst_rows, main as rmain, rows as roofline_rows
    rmain()
    return _rowdicts(
        ("arch", "shape", "status", "dominant", "compute_s", "memory_s",
         "collective_s", "roofline_fraction", "useful_flops_ratio",
         "peak_gb_per_dev"),
        roofline_rows() + gen_dst_rows())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
