"""Cross-process serving tier: worker pools, crash recovery, checkpointed
resume, HTTP front end (DESIGN.md §14).

The deterministic chaos tests drive a ``DistributedScheduler`` over
``SimWorkerPool`` — the in-process pool that runs the *same*
``worker.eval_task`` code path and applies ``harness.faultsim`` fault
plans at the same dequeue point as a real worker, with zero timing
dependence.  One test at the bottom repeats the kill scenario against
real spawned subprocesses.
"""
import numpy as np
import pytest

import jax

from harness.faultsim import FaultEvent, FaultPlan
from repro.automl.engine import AutoMLConfig
from repro.core.plan import plan
from repro.service import DistributedScheduler, SimWorkerPool, SubStratServer
from repro.service.cache import DSTCache
from repro.service.scheduler import Scheduler

PLAN = plan("gen_dst", n=24, m=4,
            sub_automl=AutoMLConfig(n_trials=4, rungs=(2, 4)),
            ft_automl=AutoMLConfig(n_trials=2, rungs=(2,)),
            psi=4, phi=10)


def _make(seed, N=48, d=6, c=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, d)).astype(np.float32)
    y = (np.arange(N) % c).astype(np.int64)
    return X, y


def _submit_two(sched):
    X1, y1 = _make(0)
    X2, y2 = _make(1)
    a = sched.submit(X1, y1, key=jax.random.key(1), plan=PLAN)
    b = sched.submit(X2, y2, key=jax.random.key(2), plan=PLAN)
    return a, b


@pytest.fixture(scope="module")
def baseline():
    """Fault-free in-process reference results for the two standard jobs."""
    sched = Scheduler(DSTCache())
    a, b = _submit_two(sched)
    sched.run()
    return {0: sched.jobs[a].result, 1: sched.jobs[b].result}


def _assert_parity(result, want):
    assert result.final.spec == want.final.spec
    np.testing.assert_allclose([v for _, v in result.final.trials],
                               [v for _, v in want.final.trials], atol=1e-6)


def _run_distributed(pool, **kw):
    sched = DistributedScheduler(pool, cache=DSTCache(), **kw)
    a, b = _submit_two(sched)
    sched.run()
    return sched, (a, b)


# ---------------------------------------------------------------------------
# fault-free parity + streamed leaderboards
# ---------------------------------------------------------------------------


def test_sim_pool_matches_in_process(baseline):
    sched, jobs = _run_distributed(SimWorkerPool(2))
    for i, j in enumerate(jobs):
        assert sched.jobs[j].phase == "done"
        _assert_parity(sched.jobs[j].result, baseline[i])
    t = sched.stats()["transport"]
    assert t["remote_tasks"] > 0
    assert t["worker_failures"] == 0


def test_leaderboard_streams_rung_by_rung(baseline):
    sched = Scheduler(DSTCache())
    a, _ = _submit_two(sched)
    server = SubStratServer(scheduler=sched)
    seen, since = [], 0
    while sched.pending():
        sched.step()
        st = server.poll(a, since=since)
        seen.extend(st.leaderboard)
        since = st.leaderboard_total
    # cursor polling delivered every entry exactly once, in order
    assert [e["rung"] for e in seen] == \
        [e["rung"] for e in sched.jobs[a].leaderboard]
    assert len(seen) >= 2                     # sub pass rungs + fine-tune
    assert seen[0]["phase"] == "sub_automl"
    assert seen[-1]["phase"] == "fine_tune"
    for entry in seen:
        accs = [t["val_acc"] for t in entry["top"]]
        assert accs == sorted(accs, reverse=True)
    # final poll with a stale cursor returns only the tail
    st = server.poll(a, since=since)
    assert st.leaderboard == ()


# ---------------------------------------------------------------------------
# chaos: deterministic kill / stall / delay recovery
# ---------------------------------------------------------------------------


def test_chaos_kill_is_deterministic_5_of_5(baseline):
    """The acceptance gate: under a fixed FaultPlan seed, kill one worker
    mid-search on every one of 5 runs — all jobs complete every time, with
    winner specs equal and accuracies within 1e-6 of the fault-free run."""
    # seed 2 deterministically compiles to "kill worker 0 at its first
    # task" — worker 0 always owns task 0 of the first rung dispatch, so
    # the kill lands mid-sub_automl on every run
    fault_plan = FaultPlan.random(seed=2, n_workers=2, actions=("kill",))
    assert fault_plan == FaultPlan.random(seed=2, n_workers=2,
                                          actions=("kill",))
    assert fault_plan.compile() == ((0, 0, "kill", 3600.0),)
    for run in range(5):
        pool = SimWorkerPool(2, fault_events=fault_plan.compile())
        sched, jobs = _run_distributed(pool)
        t = sched.stats()["transport"]
        assert t["worker_failures"] == 1, f"run {run}: kill not observed"
        for i, j in enumerate(jobs):
            assert sched.jobs[j].phase == "done", f"run {run}"
            _assert_parity(sched.jobs[j].result, baseline[i])


def test_stall_recovery_via_no_beat_timeout(baseline):
    """A stalled worker stays in alive_workers(); only the dispatched-with-
    no-beat timeout can catch it."""
    pool = SimWorkerPool(2, fault_events=FaultPlan.stall(0, 0).compile())
    sched, jobs = _run_distributed(pool, stall_timeout_s=0.05, poll_s=0.01)
    t = sched.stats()["transport"]
    assert t["worker_failures"] >= 1
    assert t["redispatched_tasks"] >= 1
    for i, j in enumerate(jobs):
        _assert_parity(sched.jobs[j].result, baseline[i])


def test_delay_does_not_trigger_recovery(baseline):
    """A slow-but-beating worker must not be declared lost."""
    pool = SimWorkerPool(2, fault_events=FaultPlan.delay(0, 0, 0.01).compile())
    sched, jobs = _run_distributed(pool, stall_timeout_s=0.05, poll_s=0.01)
    assert sched.stats()["transport"]["worker_failures"] == 0
    for i, j in enumerate(jobs):
        _assert_parity(sched.jobs[j].result, baseline[i])


def test_all_workers_dead_falls_back_locally(baseline):
    """With no survivors the front end evaluates the remainder itself."""
    fault_plan = FaultPlan.kill(0, 0) + FaultPlan.kill(1, 0)
    pool = SimWorkerPool(2, fault_events=fault_plan.compile())
    sched, jobs = _run_distributed(pool)
    t = sched.stats()["transport"]
    assert t["local_fallbacks"] >= 1
    assert sched.pool.alive_workers() == []
    for i, j in enumerate(jobs):
        assert sched.jobs[j].phase == "done"
        _assert_parity(sched.jobs[j].result, baseline[i])


# ---------------------------------------------------------------------------
# mid-pack failure isolation (the Scheduler._fail satellite)
# ---------------------------------------------------------------------------


def test_mid_pack_failure_does_not_strand_group(baseline, monkeypatch):
    """One poison job in a merged megabatch pack must fail alone: its
    co-riders re-run solo and complete (regression for the group-wide
    _fail)."""
    from repro.automl import batched

    sched = Scheduler(DSTCache())
    a, b = _submit_two(sched)
    real = batched.eval_trial_megabatch

    def poisoned(cohorts):
        # job b's cohort is poison: any dispatch containing it blows up
        ctx_b = (sched.jobs[b].search.ctx
                 if sched.jobs[b].search is not None else None)
        if ctx_b is not None and any(tc.ctx is ctx_b for tc in cohorts):
            raise RuntimeError("poison cohort")
        return real(cohorts)

    # the scheduler imports the symbol at dispatch time, so patching the
    # batched module is enough
    monkeypatch.setattr(batched, "eval_trial_megabatch", poisoned)
    sched.run()

    assert sched.jobs[b].phase == "failed"
    assert "poison" in repr(sched.jobs[b].error)
    assert sched.jobs[a].phase == "done", \
        "innocent co-rider stranded by a mid-pack failure"
    _assert_parity(sched.jobs[a].result, baseline[0])
    assert sched.poisoned_packs >= 1


def test_whole_group_failure_fails_every_job():
    """When every member also fails solo, all of them are marked failed."""
    from repro.automl import batched

    sched = Scheduler(DSTCache())
    a, b = _submit_two(sched)

    def always_broken(cohorts):
        raise RuntimeError("backend down")

    import unittest.mock as mock
    with mock.patch.object(batched, "eval_trial_megabatch", always_broken):
        sched.run()
    assert sched.jobs[a].phase == "failed"
    assert sched.jobs[b].phase == "failed"


# ---------------------------------------------------------------------------
# scheduler checkpoint / resume
# ---------------------------------------------------------------------------


def test_checkpointed_front_end_resumes_bit_identically(tmp_path, baseline):
    """Kill the front end mid-flight; a fresh scheduler restores the last
    per-step checkpoint and finishes with fault-free results."""
    ckpt = tmp_path / "ckpt"
    sched = DistributedScheduler(SimWorkerPool(2), cache=DSTCache(),
                                 ckpt_dir=ckpt)
    a, b = _submit_two(sched)
    # one step cascades factorize → dst → first sub_automl rung, so the
    # "crash" lands mid-search with one rung recorded
    sched.step()
    assert any(j.search is not None for j in sched.jobs.values()), \
        "crash point must land mid-search to exercise SearchState restore"
    del sched

    fresh = DistributedScheduler(SimWorkerPool(2), cache=DSTCache(),
                                 ckpt_dir=ckpt)
    step = fresh.resume()
    assert step == 1
    assert set(fresh.jobs) == {a, b}
    fresh.run()
    for i, j in enumerate((a, b)):
        assert fresh.jobs[j].phase == "done"
        _assert_parity(fresh.jobs[j].result, baseline[i])


def test_snapshot_preserves_leaderboard_and_counters():
    sched = Scheduler(DSTCache())
    a, b = _submit_two(sched)
    sched.run()
    blob = sched.snapshot()
    fresh = Scheduler(DSTCache())
    fresh.load_snapshot(blob)
    assert fresh.jobs[a].leaderboard == sched.jobs[a].leaderboard
    assert fresh.solo_rungs == sched.solo_rungs
    assert fresh.merged_rungs == sched.merged_rungs
    assert fresh._next_id == sched._next_id
    # the DST cache came along: a repeat submission is a hit
    X1, y1 = _make(0)
    c = fresh.submit(X1, y1, key=jax.random.key(9), plan=PLAN)
    fresh.run()
    assert fresh.jobs[c].cache_hit


# ---------------------------------------------------------------------------
# real subprocesses: spawn pool + kill + HTTP round trip
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_process_pool_chaos_with_http(baseline):
    """The kill scenario against real spawned workers, served over HTTP:
    worker 0 dies mid-protocol (os._exit), the front end re-dispatches to
    the survivor, and both jobs finish with fault-free parity."""
    from repro.service import (ProcessWorkerPool, SubStratHTTPClient,
                               SubStratHTTPServer)

    pool = ProcessWorkerPool(2, fault_events=FaultPlan.kill(0, 0).compile())
    sched = DistributedScheduler(pool, cache=DSTCache(), stall_timeout_s=60.0)
    http = SubStratHTTPServer(SubStratServer(scheduler=sched)).start()
    try:
        client = SubStratHTTPClient(http.url)
        X1, y1 = _make(0)
        X2, y2 = _make(1)
        a = client.submit(X1, y1, key=jax.random.key(1), plan=PLAN)
        b = client.submit(X2, y2, key=jax.random.key(2), plan=PLAN)
        entries = list(client.stream_leaderboard(a))
        assert len(entries) >= 2
        _assert_parity(client.result(a), baseline[0])
        _assert_parity(client.result(b), baseline[1])
        stats = client.stats()
        assert stats["transport"]["worker_failures"] == 1
        assert stats["transport"]["redispatched_tasks"] >= 1
    finally:
        http.close()
        sched.close()


def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault action"):
        FaultEvent(0, 0, "explode")
    with pytest.raises(ValueError, match=">= 0"):
        FaultEvent(-1, 0, "kill")
    compiled = FaultPlan.kill(1, 2).compile()
    assert compiled == ((1, 2, "kill", 0.0),)


def test_process_pool_refuses_off_the_cpu_backend(monkeypatch):
    """One process owns an accelerator: off the CPU backend the pool
    raises before it spawns a worker, instead of leaving the workers to
    fail and the front end to evaluate every rung itself."""
    import multiprocessing
    from repro.service import ProcessWorkerPool

    def no_spawn(*a, **k):
        raise AssertionError("a worker was spawned")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(multiprocessing, "get_context", no_spawn)
    with pytest.raises(RuntimeError, match="CPU backend only.*'tpu'"):
        ProcessWorkerPool(1)
