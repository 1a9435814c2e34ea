"""Observability subsystem: spans, metrics, jit accounting (DESIGN.md §15).

The serving-tier tests reuse test_transport's deterministic chaos setup:
``DistributedScheduler`` over ``SimWorkerPool`` with a compiled FaultPlan,
so the retry-span and heartbeat-miss assertions have zero timing
dependence.
"""
import json
import threading
import time

import numpy as np
import pytest

import jax

from harness.faultsim import FaultPlan
from repro.automl.engine import AutoMLConfig
from repro.core.plan import execute, plan
from repro.obs import jaxprof, trace
from repro.obs.metrics import MetricsRegistry
from repro.service import (
    DistributedScheduler, SimWorkerPool, SubStratHTTPClient,
    SubStratHTTPServer, SubStratServer, wire,
)
from repro.service.cache import DSTCache
from repro.service.scheduler import CohortMeta, Scheduler

PLAN = plan("gen_dst", n=24, m=4,
            sub_automl=AutoMLConfig(n_trials=4, rungs=(2, 4)),
            ft_automl=AutoMLConfig(n_trials=2, rungs=(2,)),
            psi=4, phi=10)


def _make(seed, N=48, d=6, c=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, d)).astype(np.float32)
    y = (np.arange(N) % c).astype(np.int64)
    return X, y


# ---------------------------------------------------------------------------
# spans: deterministic ids, nesting, rendering
# ---------------------------------------------------------------------------


def test_span_ids_are_deterministic_and_attempt_scoped():
    tid = trace.job_trace_id(7)
    assert tid == trace.job_trace_id(7)
    assert tid != trace.job_trace_id(8)
    a0 = trace.span_id(tid, "sub_automl/rung0", 0)
    assert a0 == trace.span_id(tid, "sub_automl/rung0", 0)
    # a retry is a *distinct* span of the same logical work
    assert a0 != trace.span_id(tid, "sub_automl/rung0", 1)
    assert a0 != trace.span_id(tid, "sub_automl/rung1", 0)


def test_span_contextvar_nesting_and_error_attr():
    sink = []
    with trace.span(sink, "t", "outer") as outer:
        with trace.span(sink, "t", "inner"):
            assert trace.current_span()["name"] == "inner"
        assert trace.current_span() is outer
    assert trace.current_span() is None
    inner, outer = sink          # children close (and append) first
    assert inner["name"] == "inner"
    assert inner["parent_id"] == outer["span_id"]
    assert outer["parent_id"] is None
    assert inner["t1"] >= inner["t0"]

    with pytest.raises(ValueError):
        with trace.span(sink, "t", "boom"):
            raise ValueError("x")
    assert sink[-1]["attrs"]["error"] is True
    assert trace.current_span() is None


def test_worker_parent_derivation_needs_no_id_exchange():
    """Both ends derive the same dispatch-span id from the wire ctx."""
    tid = trace.span_id("substrat-tasks", "0")
    ctx = trace.child_ctx(tid, "dispatch")
    front = trace.make_span(tid, "dispatch", 0.0, 1.0, attempt=2)
    remote_parent = trace.span_id(ctx["trace_id"], ctx["parent"], 2)
    assert remote_parent == front["span_id"]


def test_render_timeline_marks_retries_and_nesting():
    tid = "t"
    d0 = trace.make_span(tid, "dispatch", 0.0, 1.0, attempt=0,
                         attrs={"outcome": "lost", "worker": 0})
    d1 = trace.make_span(tid, "dispatch", 1.0, 3.0, attempt=1,
                         attrs={"outcome": "ok", "worker": 1})
    ev = trace.make_span(tid, "eval", 1.2, 2.8, attempt=1,
                         parent_id=d1["span_id"])
    out = trace.render_timeline([d0, d1, ev])
    lines = out.splitlines()
    assert len(lines) == 3
    assert "(retry #1)" in out
    assert "outcome=lost" in lines[0]
    assert lines[2].startswith("  eval (retry #1)")   # nested under d1


# ---------------------------------------------------------------------------
# metrics: exposition + bit-identical persistence
# ---------------------------------------------------------------------------


def test_metrics_exposition_and_dict():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", labels=("mode",))
    g = reg.gauge("depth", "queue depth")
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    c.inc(mode="solo")
    c.inc(2, mode="merged")
    g.set(3.5)
    h.observe(0.05)
    h.observe(5.0)
    text = reg.render()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{mode="solo"} 1' in text
    assert 'reqs_total{mode="merged"} 2' in text
    assert "depth 3.5" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text
    assert reg.to_dict()["reqs_total"]["values"] == {"merged": 2, "solo": 1}
    with pytest.raises(ValueError):
        c.inc(wrong_label=1)
    with pytest.raises(ValueError):
        reg.gauge("reqs_total", "type clash")


def test_metrics_state_roundtrip_is_bit_identical():
    reg = MetricsRegistry()
    reg.counter("a_total", "a", labels=("k",)).inc(3, k="x")
    reg.histogram("h_seconds", "h", buckets=(0.5,)).observe(0.25)
    reg.gauge("g", "g").set(1.25)
    state = reg.state_dict()
    fresh = MetricsRegistry()
    fresh.load_state(json.loads(json.dumps(state)))   # survive JSON too
    assert fresh.state_dict() == state
    assert fresh.render() == reg.render()
    # restored families stay live
    fresh.counter("a_total", "a", labels=("k",)).inc(k="x")
    assert fresh.get("a_total").value(k="x") == 4


# ---------------------------------------------------------------------------
# jaxprof: tracing counters + FLOP accounting
# ---------------------------------------------------------------------------


def test_note_trace_counts_compiles_not_calls():
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        jaxprof.note_trace("test_obs.f")
        return x * 2

    snap = jaxprof.tracing_snapshot()
    f(jnp.ones((3,))).block_until_ready()
    assert jaxprof.new_tracings_since(snap) == {"test_obs.f": 1}
    snap2 = jaxprof.tracing_snapshot()
    f(jnp.zeros((3,))).block_until_ready()     # same shape: cached
    assert jaxprof.new_tracings_since(snap2) == {}
    f(jnp.ones((4,))).block_until_ready()      # new shape: re-trace
    assert jaxprof.new_tracings_since(snap2) == {"test_obs.f": 1}


def test_pack_flops_padded_vs_useful():
    uniform = [CohortMeta(shape=(64, 16, 8, 3), steps=(4, 4))]
    padded, useful = jaxprof.pack_flops(uniform)
    assert padded == useful > 0
    mixed = [CohortMeta(shape=(64, 16, 8, 3), steps=(4,)),
             CohortMeta(shape=(32, 8, 4, 2), steps=(2,))]
    padded, useful = jaxprof.pack_flops(mixed)
    assert padded > useful          # the small cohort pays the big shape
    # both trials priced at the maximal shape and step budget
    from repro.launch.flops import tabular_trial_flops
    assert padded == 2 * tabular_trial_flops(64, 16, 8, 3, 4)


def test_prometheus_jaxprof_block_well_formed():
    text = jaxprof.render_prometheus()
    assert "# TYPE jax_jit_tracings_total counter" in text
    for line in text.splitlines():
        assert line.startswith(("#", "jax_")), line


# ---------------------------------------------------------------------------
# wire: trace-context header (v2)
# ---------------------------------------------------------------------------


def test_wire_trace_header_roundtrip():
    ctx = trace.child_ctx("abc123", "dispatch", attempt=1)
    blob = wire.dumps({"x": np.arange(3)}, kind="task", trace=ctx)
    assert wire.trace_of(blob) == ctx
    assert wire.kind_of(blob) == "task"
    np.testing.assert_array_equal(wire.loads(blob)["x"], np.arange(3))
    # absent by default — and absence is not an error
    assert wire.trace_of(wire.dumps({"x": 1})) is None


# ---------------------------------------------------------------------------
# serving tier: phase spans, poll() phase_times, snapshot persistence
# ---------------------------------------------------------------------------


def _run_one(sched):
    X, y = _make(0)
    jid = sched.submit(X, y, key=jax.random.key(1), plan=PLAN)
    sched.run()
    assert sched.jobs[jid].phase == "done"
    return jid


def test_job_spans_rebuild_the_times_ledger():
    sched = Scheduler(DSTCache())
    jid = _run_one(sched)
    job = sched.jobs[jid]
    assert job.trace_id == trace.job_trace_id(jid)
    assert all(s["trace_id"] == job.trace_id for s in job.spans)
    by_name = {}
    for s in job.spans:
        by_name.setdefault(s["name"], 0.0)
        by_name[s["name"]] += s["attrs"].get("seconds",
                                             s["t1"] - s["t0"])
    # spans cover every times key the pre-span scheduler recorded
    for name, key in (("factorize", "factorize_s"),
                      ("gen_dst", "gen_dst_s")):
        assert job.times[key] == pytest.approx(by_name[name])
    rung_total = sum(v for n, v in by_name.items()
                     if n.startswith("sub_automl/"))
    assert job.times["automl_sub_s"] == pytest.approx(rung_total)


def test_poll_reports_phase_times():
    srv = SubStratServer()
    jid = _run_one(srv.scheduler)
    st = srv.poll(jid)
    assert set(st.phase_times) == {"factorize", "gen_dst",
                                   "sub_automl", "fine_tune"}
    assert st.phase_times["gen_dst"] > 0
    assert st.phase_times["sub_automl"] > 0
    assert st.phase_times["factorize"] == \
        pytest.approx(st.times["factorize_s"])


def test_snapshot_restores_metrics_and_spans_bit_identically():
    sched = Scheduler(DSTCache())
    jid = _run_one(sched)
    blob = sched.snapshot()
    fresh = Scheduler(DSTCache())
    fresh.load_snapshot(blob)
    assert fresh.jobs[jid].spans == sched.jobs[jid].spans
    assert fresh.jobs[jid].trace_id == sched.jobs[jid].trace_id
    assert fresh.metrics.state_dict() == sched.metrics.state_dict()
    assert fresh.metrics.render() == sched.metrics.render()
    # the restored registry is live: finishing another job keeps counting
    before = fresh.metrics.get("jobs_finished_total").value(phase="done")
    _run_one(fresh)
    after = fresh.metrics.get("jobs_finished_total").value(phase="done")
    assert after == before + 1


def test_scheduler_counts_dispatches_and_cache_hits():
    sched = Scheduler(DSTCache())
    X, y = _make(0)
    a = sched.submit(X, y, key=jax.random.key(1), plan=PLAN)
    b = sched.submit(X, y, key=jax.random.key(2), plan=PLAN)  # repeat
    sched.run()
    m = sched.stats()["metrics"]
    assert m["cache_hits_total"]["value"] >= 1
    assert sum(m["dispatches_total"]["values"].values()) >= 1
    assert m["jobs_finished_total"]["values"]["done"] == 2
    assert sched.jobs[a].phase == sched.jobs[b].phase == "done"


def test_http_lock_wait_counts_a_held_lock():
    """A poll that arrives while the front end's lock is held waits for it,
    and ``http_lock_wait_seconds_total{route="/v1/poll"}`` counts the wait."""
    srv = SubStratServer()
    http = SubStratHTTPServer(srv).start()
    counter = srv.scheduler.metrics.get("http_lock_wait_seconds_total")
    replies = []
    try:
        poll = threading.Thread(target=lambda: replies.append(
            SubStratHTTPClient(http.url)._request("/v1/poll?job_id=0")))
        with http._lock:
            poll.start()
            time.sleep(0.5)        # the poll reaches its handler and waits
            t0 = time.perf_counter()
            time.sleep(0.3)
            held_s = time.perf_counter() - t0
        poll.join(timeout=30)
        assert not poll.is_alive() and replies
        assert replies[0][0] == 500    # job 0 was never submitted
        assert counter.value(route="/v1/poll") >= held_s
    finally:
        http.close()


# ---------------------------------------------------------------------------
# chaos: the killed task's re-dispatch is a visible retry span
# ---------------------------------------------------------------------------


def test_killed_task_shows_as_retry_span_with_children():
    pool = SimWorkerPool(2, fault_events=FaultPlan.kill(0, 0).compile())
    sched = DistributedScheduler(pool, cache=DSTCache())
    X, y = _make(0)
    jid = sched.submit(X, y, key=jax.random.key(1), plan=PLAN)
    sched.run()
    assert sched.jobs[jid].phase == "done"
    assert sched.metrics.get("heartbeat_misses_total").value() >= 1

    spans = sched.jobs[jid].spans
    dispatches = [s for s in spans if s["name"] == "dispatch"]
    lost = [s for s in dispatches if s["attrs"].get("outcome") == "lost"]
    retries = [s for s in dispatches if s["attempt"] > 0]
    assert lost and retries, "kill must leave a lost span and a retry span"
    assert all(s["attrs"]["outcome"] == "ok" for s in retries)
    # distinct ids: the retry is its own span of the same logical dispatch
    assert {s["span_id"] for s in lost}.isdisjoint(
        {s["span_id"] for s in retries})
    retry = retries[0]
    kids = {s["name"] for s in spans
            if s.get("parent_id") == retry["span_id"]}
    assert {"queue_wait", "eval"} <= kids
    # the rendered timeline shows it all without errors
    out = trace.render_timeline(spans)
    assert "(retry #1)" in out and "outcome=lost" in out


def test_sim_pool_spans_fold_into_job_timeline():
    sched = DistributedScheduler(SimWorkerPool(2), cache=DSTCache())
    jid = _run_one(sched)
    spans = sched.jobs[jid].spans
    names = {s["name"] for s in spans}
    assert {"dispatch", "queue_wait", "deserialize", "eval",
            "serialize"} <= names
    assert all(s["trace_id"] == sched.jobs[jid].trace_id for s in spans)
    # every worker-side span hangs off a front-end dispatch span
    ids = {s["span_id"] for s in spans}
    for s in spans:
        if s["name"] in ("deserialize", "eval", "serialize"):
            assert s["parent_id"] in ids


# ---------------------------------------------------------------------------
# one-shot path: execute(trace_sink=...) mirrors the times ledger
# ---------------------------------------------------------------------------


def test_execute_trace_sink_matches_times():
    X, y = _make(3)
    sink = []
    res = execute(PLAN, X, y, key=jax.random.key(0), trace_sink=sink)
    names = [s["name"] for s in sink]
    assert names == ["factorize", "gen_dst", "sub_automl", "fine_tune"]
    for s, key in zip(sink, ("factorize_s", "gen_dst_s",
                             "automl_sub_s", "fine_tune_s")):
        assert res.times[key] == pytest.approx(s["t1"] - s["t0"], abs=0.05)
    assert trace.render_timeline(sink)
