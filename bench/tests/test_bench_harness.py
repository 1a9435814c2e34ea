"""The benchmark harness on the CPU: lookup by name, seeded traffic, the
metric arithmetic and the contract of ``BENCHMARK.json``.  Nothing here
touches a chip or the program's server."""
import hashlib
import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run        # noqa: E402
import stats      # noqa: E402
import tables     # noqa: E402
import traffic    # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SMALL = {"n_rows": 500, "n_cols": 7, "n_classes": 3, "frac_categorical": 0.4,
         "frac_informative": 0.5, "noise": 1.0, "test_frac": 0.2}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(a.tobytes())
    return h.hexdigest()


# -- lookup by name ----------------------------------------------------------


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_configs_load_by_name(name):
    cfg = run.load_config(SPEC, name)
    assert cfg["name"] == name
    assert {"table", "plan", "server", "clients", "check_jobs"} <= set(cfg)


def test_limits_load_by_name_and_cover_the_check():
    for w in SPEC["workloads"]:
        limits = run.load_limits(w["name"])
        assert {"fitness_gap", "ft_prob_gap", "faults"} <= set(limits)
        assert limits["faults"] == 0


@pytest.mark.parametrize("name", sorted({w["traffic"]
                                         for w in SPEC["workloads"]}))
def test_traffic_loads_by_name(name):
    assert traffic.load(name)["tables"] in ("fresh", "pool")


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["end_to_end"]
                                  + SPEC["per_layer"]])
def test_every_metric_has_a_reader(name):
    assert callable(run.reader(name))


@pytest.mark.parametrize("lookup", [
    lambda: run.load_config(SPEC, "no-such-config"),
    lambda: traffic.load("no_such_mix"),
    lambda: run.reader("no_such_metric"),
    lambda: run.load_limits("no.such.cell"),
    lambda: run.run_cell("no.such.cell", 1, 1.0, False, bench=SPEC),
])
def test_unknown_names_fail(lookup):
    with pytest.raises(KeyError):
        lookup()


def test_no_tpu_is_fatal():
    import jax
    with pytest.raises(SystemExit):
        run.require_chips(jax, 1)


# -- seeded traffic ------------------------------------------------------------


def _window(mix, seed, n, clients=1, client=0):
    return [traffic.window_entry(mix, seed, client, k, clients)
            for k in range(n)]


def test_same_seed_same_tables():
    fresh = traffic.load("fresh")
    a = [tables.job_tables(SMALL, e["table"])
         for e in _window(fresh, 2**31 + 7, 4)]
    b = [tables.job_tables(SMALL, e["table"])
         for e in _window(fresh, 2**31 + 7, 4)]
    assert [_digest(t) for t in a] == [_digest(t) for t in b]


def test_fresh_tables_are_distinct_until_the_catalog_is_spent():
    fresh = traffic.load("fresh")
    size = fresh["catalog"]
    seq = _window(fresh, 5, 2 * size)
    assert [e["entry"] for e in seq[:size]] == list(range(size))
    assert len({e["table"] for e in seq[:size]}) == size
    assert len({e["key"] for e in seq[:size]}) == size
    assert seq[size:] == seq[:size]            # then the cycle comes round
    digests = {_digest(tables.job_tables(SMALL, e["table"]))
               for e in seq[:size]}
    assert len(digests) == size


def test_fresh_cycle_misses_a_one_entry_cache():
    """Set-up serves the catalog in order, then the window cycles it: no job
    meets the table just before it, the only one a one-entry DST cache
    holds, so every job misses the cache."""
    fresh = traffic.load("fresh")
    assert fresh["server"] == {"cache_capacity": 1}
    seq = ([e["table"] for e in traffic.warmup_entries(fresh, 0, 1)]
           + [e["table"] for e in _window(fresh, 2**35 + 1, 40)])
    assert all(a != b for a, b in zip(seq, seq[1:]))


def test_every_seed_sends_the_same_work():
    fresh = traffic.load("fresh")
    assert _window(fresh, 5, 20) == _window(fresh, 2**40 + 6, 20)
    pool = traffic.load("recurring")
    n = 3 * pool["block"]
    a = [e["entry"] for e in _window(pool, 5, n)]
    b = [e["entry"] for e in _window(pool, 2**40 + 6, n)]
    assert a != b                   # another order in each block
    for i in range(0, n, pool["block"]):
        assert sorted(a[i:i + pool["block"]]) == sorted(b[i:i + pool["block"]])


def test_tenants_deal_the_sequence_round():
    fresh = traffic.load("fresh")
    one = [e["entry"] for e in _window(fresh, 9, 8)]
    two = [_window(fresh, 9, 4, clients=2, client=c) for c in range(2)]
    assert one == [two[k % 2][k // 2]["entry"] for k in range(8)]


def test_recurring_pool_repeats_its_four():
    mix = traffic.load("recurring")
    warm = [e["table"] for c in range(2)
            for e in traffic.warmup_entries(mix, c, 2)]
    assert len(warm) == len(set(warm)) == mix["catalog"]
    block = mix["block"]
    window = [e["table"] for e in _window(mix, 2**33 + 1, 2 * block)]
    assert set(window) == set(warm)
    ranked = [traffic.catalog_entry(i)["table"] for i in range(len(warm))]
    counts = [window[:block].count(t) for t in ranked]
    assert counts == sorted(counts, reverse=True) and counts[0] > counts[-1]
    assert sum(counts) == block
    other = [e["table"] for e in _window(mix, 2**33 + 2, block)]
    assert sorted(other) == sorted(window[:block])   # same work per block


def test_fresh_mix_warms_every_table_once():
    fresh = traffic.load("fresh")
    warm = [e["entry"] for c in range(2)
            for e in traffic.warmup_entries(fresh, c, 2)]
    assert sorted(warm) == list(range(fresh["catalog"]))


def test_mix_server_settings_reach_the_server():
    cfg = run.load_config(SPEC, SPEC["configs"][0]["name"])
    for mix, capacity in ((traffic.load("fresh"), 1),
                          (traffic.load("recurring"),
                           cfg["server"]["cache_capacity"])):
        server, http = run.build_server(cfg, mix)
        try:
            assert server.scheduler.cache.capacity == capacity
        finally:
            http.close()


def _done_job(i, table, dur=1.0):
    return {"k": i, "table": table, "status": "done", "t_submit": float(i),
            "t_done": float(i) + dur}


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 2**40 + 9])
def test_check_sample_holds_every_table(seed):
    """A pool window, with its rarest table in two jobs: a sample of four
    holds the longest job and one job of each of the four tables."""
    tables_ = [0] * 10 + [1] * 5 + [2] * 3 + [3] * 2
    jobs = [_done_job(i, t) for i, t in enumerate(tables_)]
    jobs[7]["t_done"] += 5.0                       # the longest
    jobs.append({"k": 99, "table": 3, "status": "failed"})
    got = run.sample_jobs(jobs, 4, seed)
    assert sorted(r["table"] for r in got) == [0, 1, 2, 3]
    assert jobs[7] in got
    more = run.sample_jobs(jobs, 6, seed)
    assert len(more) == 6 and {r["table"] for r in more} == {0, 1, 2, 3}
    assert len({r["k"] for r in more}) == 6


def test_large_seed_is_accepted():
    seed = tables.table_seed(2**40 + 3, 1, 2)
    assert 0 <= seed < 2**32


# -- metric arithmetic on hand-made records -----------------------------------


def _rec():
    def job(t_submit, dur, acc, status="done", sub=0.1):
        r = {"t_submit": t_submit, "t_submitted": t_submit + sub,
             "t_done": t_submit + dur, "status": status,
             "wall_submitted": 1000.0 + t_submit + sub,
             "spans": [{"name": "factorize", "t0": 1000.0 + t_submit + sub
                        + 0.5}],
             "phase_times": {"factorize": 1.0, "gen_dst": 2.0,
                             "sub_automl": 3.0, "fine_tune": 4.0}}
        if status == "done":
            r["result"] = {"final": {"test_acc": acc}}
        return r

    jobs = [job(float(i), float(d), 0.9 + 0.01 * i)
            for i, d in enumerate([1, 2, 3, 4, 5, 6, 7, 8, 9, 10])]
    jobs.append(job(50.0, 30.0, 0.5))            # finishes after the window
    jobs.append(job(1.0, 1.0, None, status="failed"))
    counters0 = {"dispatches_total": {"kind": "counter",
                                      "values": {"merged": 2.0, "solo": 1.0}},
                 "pack_padded_flops_total": {"kind": "counter", "value": 10.0},
                 "pack_useful_flops_total": {"kind": "counter", "value": 5.0}}
    counters1 = {"dispatches_total": {"kind": "counter",
                                      "values": {"merged": 12.0, "solo": 6.0}},
                 "pack_padded_flops_total": {"kind": "counter", "value": 40.0},
                 "pack_useful_flops_total": {"kind": "counter", "value": 20.0}}
    return SimpleNamespace(jobs=jobs, t0=0.0, t1=60.0, seconds=60.0,
                           counters0=counters0, counters1=counters1,
                           setup_s=12.5, compile_s=3.25, trace=None,
                           setup_jobs=[{}] * 4, setup_compile_s=10.0)


@pytest.mark.parametrize("name, want", [
    # every job submitted in the window counts, the late one included
    ("job_p50_s", 6.0),
    ("jobs_per_min", 60.0 * 11 / 80.0),     # 11 finished by t = 80 s
    ("test_acc", (sum(0.9 + 0.01 * i for i in range(10)) + 0.5) / 11),
    ("submit_s", 0.1),
    ("queue_s", 0.5),
    ("dispatches_per_job", 15.0 / 11),
    ("setup_s", 12.5),
    ("compile_s", 3.25),
    ("table_compile_s", 2.5),                # set-up compiling, per job
    ("phase_s.factorize", 1.0),
    ("phase_s.fine_tune", 4.0),
    ("device_idle_share", None),            # no trace: nothing to read
    ("gen_dst_roofline", None),
])
def test_metric_arithmetic(name, want):
    got = run.reader(name)(_rec())
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_percentile_is_inclusive_quantile():
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.percentile([], 50) is None


# -- the contract of BENCHMARK.json --------------------------------------------


def _metrics():
    return SPEC["end_to_end"] + SPEC["per_layer"]


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [m["name"] for m in _metrics()])
    for name in names:
        assert NAME.match(name), name
    for m in _metrics():
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in _metrics()}) == len(_metrics())
    assert len({w["name"] for w in SPEC["workloads"]}) == len(SPEC["workloads"])


def _reported(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_moves_is_reported_by_each_cell():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for w in SPEC["workloads"]:
            if _reported(m, w["name"]):
                assert _reported(e2e[m["moves"]], w["name"]), (m["name"], w)


def test_every_cell_reports_enough():
    for w in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if _reported(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(_reported(m, w["name"]) for m in SPEC["per_layer"])
        assert w["chips"] == 1


def test_bounds_and_run_length():
    assert 1 <= SPEC["run_seconds"] <= 51
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_configs_and_paths():
    assert SPEC["paths"] == ["bench"]
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/") and c["name"] in used
        cfg = json.loads((BENCH.parent / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] == []


def test_rooflines_are_percent():
    for m in _metrics():
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
