"""The benchmark: one cell of ``BENCHMARK.json`` on the chips of this host.

    python3 bench/run.py --workload d1.fresh --seed 7 --seconds 40 --trace 0

One process holds the chip: it starts the served path in-process
(``SubStratServer`` behind ``SubStratHTTPServer`` on localhost) and reads
its spans, counters and compile events; the tenants run in a child process
under ``JAX_PLATFORMS=cpu`` (``loadgen.py``) and reach the server only over
HTTP.  A run:

1. set-up (``setup_s``): imports; the window's server, and on it one job
   for each table of the mix's catalog, which compiles, or loads from the
   persistent cache, every program the window will run (a job's programs
   depend on its table's contents, not only on its shape);
2. the window: ``--seconds`` of closed-loop traffic; then the jobs still in
   flight are waited for;
3. the check: the plain reference (``reference.py``) recomputes the
   answers of a seeded sample of the window's jobs; every number compared is
   printed beside its limit, on standard error and under ``checks`` in the
   result;
4. the result: one JSON line, the last of standard output.  ``--trace 0``
   reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
   metrics, read from a profile of part of the window.

Each metric is computed by ``bench/metrics/<name>.py`` from the run's
records; a configuration is ``bench/configs/<name>.json``, a traffic mix
``bench/traffic/<name>.json``, the limits of a cell's check
``bench/limits/<cell>.json``.  The run fails, and prints no result, unless
JAX sees a TPU with as many chips as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse   # noqa: E402
import gc         # noqa: E402
import gzip       # noqa: E402
import importlib.util  # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import queue      # noqa: E402
import shutil     # noqa: E402
import subprocess  # noqa: E402
import sys        # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import tables as _tables          # noqa: E402
import trace_reduce               # noqa: E402
import traffic as _traffic        # noqa: E402

RUN_DIR = HERE / ".runs"          # scratch of the current run, in the checkout
READY_TIMEOUT_S = 900.0           # set-up, compile included
DRAIN_TIMEOUT_S = 300.0
TRACE_SECONDS = 6.0               # profiled stretch of the window
TRACE_OFFSET = 0.3                # ... starting this far into the window


# tracing, lowering, and XLA compilation or loading from the persistent cache
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spends tracing and lowering a program and compiling it
    or loading it from the persistent cache, and the cache's hits, over
    every thread."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1


def load_bench(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((ROOT / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_limits(workload: str) -> dict:
    """``bench/limits/<workload>.json``: the limit of each number the cell
    compares with the reference (PERF.md gives the readings behind them)."""
    path = HERE / "limits" / f"{workload}.json"
    if not path.is_file():
        raise KeyError(f"no limits for workload {workload!r} ({path} is "
                       f"missing)")
    return json.loads(path.read_text())


def cell_metrics(bench: dict, workload: str, trace: bool):
    """The metric entries this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(rec)``; an unknown name fails."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(jax, chips: int) -> dict:
    """The devices JAX sees; anything but ``chips`` or more TPUs is fatal."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"this cell needs {chips} TPU chip(s); JAX sees "
                         f"{len(devices)} {dev.platform} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def enable_cache(jax) -> None:
    """The program's persistent compilation cache (``.jax_cache/`` in the
    checkout, or ``$JAX_COMPILATION_CACHE_DIR``), keeping every program
    however fast it compiled, so that a later run of the cell loads all of
    them instead of compiling the quick ones again."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def build_server(cfg: dict, mix: dict):
    """The configuration's server, with the settings the mix asks for."""
    from repro.service import SubStratHTTPServer, SubStratServer
    server = SubStratServer(**{**cfg["server"], **mix.get("server", {})})
    return server, SubStratHTTPServer(server).start()


def _metrics_snapshot(http) -> dict:
    with http._lock:
        return http.server.scheduler.metrics.to_dict()


class Child:
    """The tenants' process and its line protocol (see ``loadgen.py``)."""

    def __init__(self, spec: dict):
        RUN_DIR.mkdir(parents=True, exist_ok=True)
        self.spec_path = RUN_DIR / "loadgen_spec.json"
        self.spec_path.write_text(json.dumps(spec))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py"), str(self.spec_path)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
        self.lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line.strip())
        self.lines.put(None)

    def line(self, timeout_s: float):
        """The child's next line, or ``""`` if none came in ``timeout_s``."""
        try:
            line = self.lines.get(timeout=timeout_s)
        except queue.Empty:
            return ""
        if line is None:
            raise RuntimeError(f"load generator ended (exit "
                               f"{self.proc.wait()})")
        return line

    def expect(self, word: str, timeout_s: float) -> list:
        line = self.line(timeout_s)
        if line.split()[:1] != [word]:
            raise RuntimeError(f"load generator: expected {word!r}, got "
                               f"{line!r}")
        return line.split()[1:]

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             bench: dict | None = None, require=require_chips) -> dict:
    """Run one cell; returns the result object (``print_result`` prints)."""
    import jax
    bench = bench or load_bench()
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    metrics = cell_metrics(bench, workload, trace)
    readers = {m["name"]: reader(m["name"]) for m in metrics}
    device = require(jax, cell["chips"])
    cfg = load_config(bench, cell["config"])
    mix = _traffic.load(cell["traffic"])
    load_limits(workload)

    enable_cache(jax)
    clock = CompileClock(jax)
    shutil.rmtree(RUN_DIR, ignore_errors=True)

    child = Child({"config": cfg, "traffic": mix, "seed": seed,
                   "out": str(RUN_DIR / "records.json")})
    http = None
    try:
        server, http = build_server(cfg, mix)
        child.send(f"WARMUP {http.url}")
        child.expect("READY", READY_TIMEOUT_S)
        c0, hits0 = clock.seconds, clock.cache_hits
        counters0 = _metrics_snapshot(http)
        t0 = time.monotonic()
        t1 = t0 + seconds
        setup_s = t0 - T_START
        child.send(f"GO {http.url} {t1!r}")
        trace_events = None
        if trace:
            time.sleep(min(TRACE_OFFSET, seconds / 4))
            trace_events = _profile(jax, min(TRACE_SECONDS, seconds / 2))
        time.sleep(max(0.0, t1 - time.monotonic()))
        child.expect("DONE", DRAIN_TIMEOUT_S + seconds)
        counters1 = _metrics_snapshot(http)
        compile_s, hits = clock.seconds - c0, clock.cache_hits - hits0
        child.proc.wait(timeout=60)
        out = json.loads((RUN_DIR / "records.json").read_text())
        device["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices())
        jobs = [r for r in out["records"] if r["stage"] == "window"]
        setup_jobs = [r for r in out["records"] if r["stage"] == "warmup"]
        with http._lock:
            for r in jobs + setup_jobs:
                if "job_id" in r:
                    job = server.scheduler.jobs[r["job_id"]]
                    r["spans"] = list(job.spans)
                    r["phase_times"] = server.poll(r["job_id"]).phase_times
                    r["cache_hit"] = bool(job.cache_hit)
    finally:
        child.kill()
        if http is not None:
            http.close()
    del server, http
    gc.collect()

    print(f"set-up {setup_s:.1f} s, {len(setup_jobs)} jobs, compile "
          f"{c0:.3f} s; window {len(jobs)} jobs, compile {compile_s:.3f} s, "
          f"{hits} cache loads",
          file=sys.stderr, flush=True)
    rec = SimpleNamespace(
        workload=workload, config=cfg, t0=t0, t1=t1, setup_s=setup_s,
        jobs=jobs, setup_jobs=setup_jobs, counters0=counters0,
        counters1=counters1, compile_s=compile_s, setup_compile_s=c0,
        trace=trace_events,
        device=device)
    checks = check_outputs(rec, seed)
    values = {}
    for m in metrics:
        v = readers[m["name"]](rec)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(jobs),
        "failed": sum(1 for r in jobs if r["status"] != "done"),
        "metrics": values,
        "device": device,
    }
    if trace and trace_events is not None:
        red = trace_reduce.reduce(trace_events)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    return result


def _profile(jax, seconds: float):
    """Profile ``seconds`` of the window; returns the trace's events."""
    import glob
    tdir = RUN_DIR / "trace"
    jax.profiler.start_trace(str(tdir))
    time.sleep(seconds)
    jax.profiler.stop_trace()
    paths = sorted(glob.glob(str(tdir / "plugins" / "profile" / "*" /
                                 "*.xplane.pb")))
    events = trace_reduce.load(paths[-1]) if paths else None
    shutil.rmtree(tdir, ignore_errors=True)
    if events is not None:   # what the reduction reads, kept for a look
        (RUN_DIR / "trace_events.json.gz").write_bytes(gzip.compress(
            json.dumps(trace_reduce.compact(events)).encode()))
    return events


def sample_jobs(jobs, n: int, seed: int):
    """A seeded sample of ``n`` of the jobs that finished: the longest, then
    one job of each table not yet in the sample, then any others."""
    done = [r for r in jobs if r["status"] == "done"]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: done[i]["t_done"] - done[i]["t_submit"])
    rng = np.random.default_rng(_tables.table_seed(seed, 99))
    order = [int(i) for i in rng.permutation(len(done))]
    picked, seen = [longest], {done[longest]["table"]}
    for i in order:
        if len(picked) < n and done[i]["table"] not in seen:
            picked.append(i)
            seen.add(done[i]["table"])
    picked += [i for i in order if i not in picked][:max(0, n - len(picked))]
    return [done[i] for i in sorted(picked)]


def check_outputs(rec, seed: int, mode: str = "reference") -> dict:
    """The numbers compared with the reference, each with its limit.  With
    another ``mode`` than ``"reference"`` the reference computed in that
    mode (``reference.MODES``) takes the place of the jobs' answers."""
    import reference
    cfg = rec.config
    winners = {}   # the winner family each table's sub-AutoML pass found
    for r in rec.setup_jobs + rec.jobs:
        if r["status"] == "done" and not r.get("cache_hit"):
            winners[r["table"]] = r["result"]["intermediate"]["spec"]["family"]
    worst = {}
    faults = []
    for r in sample_jobs(rec.jobs, cfg["check_jobs"], seed):
        X, y, _, _ = _tables.job_tables(cfg["table"], r["table"])
        sub = not r.get("cache_hit")
        ref = reference.answers(r, X, y, cfg["plan"], sub_pass=sub)
        got = (reference.served_answers(r) if mode == "reference" else
               reference.answers(r, X, y, cfg["plan"], sub_pass=sub,
                                 mode=mode))
        for k, v in reference.compare(got, ref).items():
            worst[k] = max(worst.get(k, 0.0), v)
        faults += reference.faults(r, X, y, cfg["plan"], sub_pass=sub,
                                   winner_family=winners.get(r["table"]))
    n_failed = sum(1 for r in rec.jobs if r["status"] != "done")
    if not rec.jobs or n_failed:
        faults.append(f"{n_failed} of {len(rec.jobs)} jobs failed")
    limits = load_limits(rec.workload)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in worst.items()}
    checks["faults"] = {"value": len(faults), "limit": limits["faults"],
                        "what": faults[:5]}
    return checks


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
