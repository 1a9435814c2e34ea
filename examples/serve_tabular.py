"""Serving walkthrough: the multi-tenant SubStrat job server.

    PYTHONPATH=src python examples/serve_tabular.py [--jobs 4] [--scale 0.3]
                                                    [--trials 8] [--workers 2]
                                                    [--kill-worker 0]
                                                    [--json out.json]

Submits ``--jobs`` AutoML jobs in same-dataset pairs over two tabular
datasets — so every odd job is a repeat submission — from two tenants,
drives the scheduler,
and prints what the service layer did for each job: which phases ran, which
were skipped by the DST cache (``gen_dst`` becomes a lookup) or warm-start
(the sub-AutoML pass is skipped when the winner family is already known),
and how rung cohorts from concurrent jobs merged into shared batched
dispatches.  Ends with the per-tenant accounting and a budget-rejection
demo.  ``--jobs 2 --scale 0.1 --trials 4`` is the CI smoke configuration
(job 1 is a cache-hit repeat of job 0).

With ``--workers N`` the same jobs run on the cross-process serving tier
instead: rung evaluations ship over the versioned wire format to ``N``
worker subprocesses (DESIGN.md §14).  ``--kill-worker W [--kill-task T]``
injects a deterministic crash — worker ``W`` exits hard when it dequeues
its ``T``-th task — and the front end detects the loss, re-dispatches the
orphaned cohorts to the survivors, and still produces the fault-free
answer.  ``--json PATH`` writes per-job results (winner family, test
accuracy, trial accuracies) so a chaos run can be diffed against a
fault-free run; the CI chaos gate does exactly that.

Every run ends with the observability surface (DESIGN.md §15): one job's
span timeline (on chaos runs, the job whose killed task re-dispatched —
the retry shows as a distinct ``(retry #1)`` span with its own queue-wait
and eval children) and the full Prometheus exposition that ``GET
/v1/metrics`` serves, including ``heartbeat_misses_total`` after a kill.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

from repro.automl.engine import AutoMLConfig  # noqa: E402
from repro.core.gen_dst import GenDSTConfig  # noqa: E402
from repro.core.plan import plan  # noqa: E402
from repro.data.tabular import PAPER_DATASETS, make_dataset, train_test_split  # noqa: E402
from repro.obs.trace import render_timeline  # noqa: E402
from repro.service import (  # noqa: E402
    BudgetExceeded, DistributedScheduler, ProcessWorkerPool, SubStratServer,
)
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=4,
                    help="submissions, paired over 2 datasets (odd jobs are "
                         "repeats of the preceding even job's dataset)")
    ap.add_argument("--scale", type=float, default=0.3,
                    help="dataset row-count scale (0.1 = smoke size)")
    ap.add_argument("--trials", type=int, default=8,
                    help="AutoML trial budget of the sub pass")
    ap.add_argument("--workers", type=int, default=0,
                    help="run rung evaluation on N worker subprocesses "
                         "(0 = in-process scheduler, the default)")
    ap.add_argument("--kill-worker", type=int, default=None, metavar="W",
                    help="chaos: worker W exits hard when it dequeues its "
                         "--kill-task'th task (requires --workers)")
    ap.add_argument("--kill-task", type=int, default=0, metavar="T",
                    help="which dequeue of worker W triggers the kill "
                         "(default 0 = its first task)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-job results (family, test acc, trial "
                         "accuracies) + transport stats as JSON for parity "
                         "diffs between chaos and fault-free runs")
    args = ap.parse_args()
    if args.kill_worker is not None and args.workers <= 0:
        ap.error("--kill-worker requires --workers >= 1")

    datasets = []
    for name in ("D3", "D6"):
        X, y = make_dataset(PAPER_DATASETS[name], scale=args.scale)
        Xtr, ytr, Xte, yte = train_test_split(X, y)
        datasets.append((name, Xtr, ytr, Xte, yte))

    p = plan(
        "gen_dst", cfg=GenDSTConfig(psi=8, phi=20),
        sub_automl=AutoMLConfig(n_trials=args.trials, rungs=(30, 80)),
        ft_automl=AutoMLConfig(n_trials=4, rungs=(80,)),
    )

    if args.workers > 0:
        # fault events are primitive tuples (worker, task, action, seconds) —
        # the same shape tests/harness/faultsim.py compiles FaultPlans to
        events = ()
        if args.kill_worker is not None:
            events = ((args.kill_worker, args.kill_task, "kill", 0.0),)
            print(f"chaos: worker {args.kill_worker} will exit at its "
                  f"task #{args.kill_task}")
        print(f"starting {args.workers} worker subprocess(es)...", flush=True)
        pool = ProcessWorkerPool(args.workers, fault_events=events)
        srv = SubStratServer(
            scheduler=DistributedScheduler(pool, stall_timeout_s=120.0))
    else:
        srv = SubStratServer()

    ids = []
    for i in range(args.jobs):
        name, Xtr, ytr, Xte, yte = datasets[(i // 2) % len(datasets)]
        jid = srv.submit(Xtr, ytr, tenant=("acme" if i % 2 == 0 else "globex"),
                         key=jax.random.key(i), plan=p,
                         X_test=Xte, y_test=yte)
        ids.append((jid, name))
        print(f"submitted job {jid} ({name}, tenant "
              f"{'acme' if i % 2 == 0 else 'globex'})")

    try:
        srv.run()

        print("\njob  dataset  phase  dst      sub-automl  result")
        records = []
        for jid, name in ids:
            st = srv.poll(jid)
            res = srv.result(jid)
            dst = ("cache-hit" if st.cache_hit else
                   f"{st.times['gen_dst_s']:.2f}s")
            sub = ("warm-start" if st.warm_started else
                   f"{st.times.get('automl_sub_s', 0.0):.2f}s")
            print(f"{jid:>3}  {name:>7}  {st.phase:>5}  {dst:>8}  {sub:>10}  "
                  f"{res.final.spec.family}, test-acc "
                  f"{res.final.test_acc:.3f}, {res.total_time_s:.2f}s")
            records.append({
                "job": jid, "dataset": name,
                "family": res.final.spec.family,
                "preproc": res.final.spec.preproc,
                "test_acc": float(res.final.test_acc),
                "trials": [float(v) for _, v in res.final.trials],
                "sub_trials": [float(v) for _, v in res.intermediate.trials],
            })

        stats = srv.stats()
        print(f"\ncache: {stats['cache']['hits']} hits / "
              f"{stats['cache']['misses']} misses, {stats['cache']['size']} DSTs")
        print(f"rung dispatches: {stats['merged_rungs']} merged "
              f"(covering {stats['merged_jobs']} job-rungs, "
              f"{stats['hetero_rungs']} shape-padded), "
              f"{stats['solo_rungs']} solo")
        if "transport" in stats:
            tr = stats["transport"]
            print(f"transport: {tr['remote_tasks']} remote tasks, "
                  f"{tr['worker_failures']} worker failures, "
                  f"{tr['redispatched_tasks']} re-dispatched, "
                  f"{tr['workers_alive']}/{tr['workers_total']} workers alive")
        for tenant, acc in stats["tenants"].items():
            print(f"tenant {tenant}: {acc['jobs_submitted']} jobs, "
                  f"{acc['spent_s']:.2f}s compute")

        # trace timeline: prefer a job with a visible retry span (chaos runs)
        # so the killed task's re-dispatch is what gets shown
        tl_jid = ids[0][0]
        for jid, _ in ids:
            tr = srv.trace(jid)
            if tr and any(s.get("attempt", 0) > 0 for s in tr["spans"]):
                tl_jid = jid
                break
        tr = srv.trace(tl_jid)
        print(f"\ntrace timeline (job {tl_jid}, trace {tr['trace_id']}):")
        print(render_timeline(tr["spans"]))

        print("\n/v1/metrics exposition:")
        print(srv.metrics_text())

        if args.json:
            payload = {"jobs": records,
                       "transport": stats.get("transport")}
            Path(args.json).write_text(json.dumps(payload, indent=2))
            print(f"wrote {args.json}")
    finally:
        if hasattr(srv.scheduler, "close"):
            srv.scheduler.close()

    # budget accounting: a tenant over its budget is refused at submit
    srv.set_budget("acme", 1e-6)
    _, Xtr, ytr, *_ = datasets[0]
    try:
        srv.submit(Xtr, ytr, tenant="acme", plan=p)
    except BudgetExceeded as e:
        print(f"\nbudget rejection works: {e}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
