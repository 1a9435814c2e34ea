"""CI gate for cross-tenant portfolio warm-starts (DESIGN.md §17).

    PYTHONPATH=src python examples/check_warm_start.py

Serves N history jobs cold on one scheduler, snapshots it, restores a
fresh scheduler from the snapshot (server restart), and asserts:

1. the experience store survives the snapshot bit-identically (wire-bytes
   equal) and the restored store yields byte-for-byte the same portfolio
   decision as the live one;
2. the restarted, warm-started server reaches the cold baseline's winner
   accuracy on every new job in *strictly fewer* dispatched sub-AutoML
   trials;
3. ``/v1/metrics`` (``SubStratServer.metrics_text()``) reports nonzero
   ``portfolio_hits_total`` and ``portfolio_trials_saved_total``.

Everything is seeded; a failure is a real regression, not flake.
"""
import sys

sys.path.insert(0, "src")

import numpy as np  # noqa: E402

from repro.automl.engine import AutoMLConfig  # noqa: E402
from repro.core.plan import plan  # noqa: E402
from repro.meta import portfolio_for  # noqa: E402
from repro.service import SubStratServer, wire  # noqa: E402
from repro.service.scheduler import Scheduler  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def make_data(seed: int, N: int = 400, d: int = 8):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, N)
    X = np.column_stack([y * 1.5 + rng.normal(0, 0.8, N) for _ in range(d)])
    return X, y


def serve(scheduler: Scheduler, datasets, p):
    ids = [scheduler.submit(X, y, plan=p) for X, y in datasets]
    scheduler.run()
    results = []
    for jid in ids:
        job = scheduler.jobs[jid]
        assert job.phase == "done", f"job {jid} failed: {job.error!r}"
        results.append(job.result)
    return results


def main() -> None:
    automl = AutoMLConfig(n_trials=10, rungs=(8, 16))
    cold_plan = plan("mc", budget=200, fine_tune=False, sub_automl=automl,
                     warm_start=False)
    warm_plan = plan("mc", budget=200, fine_tune=False, sub_automl=automl)
    history = [make_data(300 + i) for i in range(4)]
    evals = [make_data(400 + i) for i in range(4)]

    # -- history phase, then a server restart from the snapshot ------------
    hist = Scheduler(warm_min_history=len(history) + 1)
    serve(hist, history, warm_plan)
    blob = hist.snapshot()
    restored = Scheduler()
    restored.load_snapshot(blob)

    live_bytes = wire.dumps(hist.experience.state_dict())
    rest_bytes = wire.dumps(restored.experience.state_dict())
    assert live_bytes == rest_bytes, \
        "experience store changed across snapshot/restore"
    qX, qy = evals[0]
    from repro.core.measures import factorize
    from repro.meta import meta_features
    feats = meta_features(factorize(qX, qy))
    for store in (hist.experience, restored.experience):
        assert store.n_trained() == len(history), store.n_trained()
    p_live = portfolio_for(hist.experience, feats, k=6, knn=4)
    p_rest = portfolio_for(restored.experience, feats, k=6, knn=4)
    assert p_live == p_rest, "portfolio decision changed across restore"
    print(f"snapshot round-trip OK: {len(history)} trained fingerprints, "
          f"portfolio of {len(p_live)} specs identical")

    # -- cold baseline on fresh datasets -----------------------------------
    cold = serve(Scheduler(), evals, cold_plan)
    cold_accs = [float(r.intermediate.val_acc) for r in cold]
    cold_trials = [r.intermediate.n_trials for r in cold]

    # -- warm serving on the restarted scheduler ---------------------------
    warm_server = SubStratServer(scheduler=restored)
    ids = [warm_server.submit(X, y, plan=warm_plan) for X, y in evals]
    warm = [warm_server.result(jid) for jid in ids]
    warm_trials = [r.intermediate.n_trials for r in warm]
    for i, (r, target) in enumerate(zip(warm, cold_accs)):
        acc = float(r.intermediate.val_acc)
        assert acc >= target - 1e-6, \
            f"warm job {i}: {acc} < cold winner {target}"
    assert sum(warm_trials) < sum(cold_trials), \
        f"warm dispatched {sum(warm_trials)} trials, cold " \
        f"{sum(cold_trials)} — no savings"
    print(f"warm run OK: reached all {len(evals)} cold winner accuracies "
          f"in {sum(warm_trials)} trials vs cold {sum(cold_trials)}")

    # -- the metrics surface saw it ----------------------------------------
    text = warm_server.metrics_text()

    def metric_value(name: str) -> float:
        total = 0.0
        for line in text.splitlines():
            if line.startswith(name) and " " in line:
                total += float(line.rsplit(" ", 1)[1])
        return total

    hits = metric_value("portfolio_hits_total")
    saved = metric_value("portfolio_trials_saved_total")
    assert hits == len(evals), f"portfolio_hits_total {hits} != {len(evals)}"
    assert saved > 0, "portfolio_trials_saved_total is zero"
    print(f"metrics OK: portfolio_hits_total={hits:.0f}, "
          f"portfolio_trials_saved_total={saved:.0f}")
    print("warm-start gate PASS")


if __name__ == "__main__":
    enable_compile_cache()
    main()
