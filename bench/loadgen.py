"""Closed-loop load generator: tenants that submit a job and wait for it.

    JAX_PLATFORMS=cpu python bench/loadgen.py <spec.json>

``bench/run.py`` starts this as a child process with ``JAX_PLATFORMS=cpu``,
so it never touches the chip: the harness process owns the chip and the
servers, this process owns the tenants.  Making a table and encoding it for
the wire happen here, off the server's interpreter lock.

The spec names the cell's configuration and traffic, the run seed and where
to write the records.  ``clients`` threads each run a closed loop with no
think time: submit, wait for the result, submit the next.  Commands arrive
on standard input, one line each; the answers go to standard output:

- ``WARMUP <url>``: every tenant submits its set-up tables (each catalog
  table once, ``traffic.py``); answer ``READY``;
- ``GO <url> <t_end>``: every tenant serves its window sequence from its
  start until ``t_end`` (``time.monotonic()`` seconds; the clock is shared
  by the processes of the machine), then waits for the jobs in flight; the
  records are written as JSON and the answer is ``DONE``.

Tables are made once per catalog entry and kept: the set-up tables in the
background from the start, while the parent imports and starts its server,
so the window sends the tables set-up made.
"""
from __future__ import annotations

import json
import sys
import threading
import time
import traceback
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import tables as _tables    # noqa: E402
import traffic as _traffic  # noqa: E402

RESULT_TIMEOUT_S = 240.0


def build_plan(plan_cfg: dict):
    """The job's ``Plan``: every budget pinned from the configuration; how
    the work runs (Gen-DST backend, batching) is left to the program."""
    from repro.automl.engine import AutoMLConfig
    from repro.core.gen_dst import GenDSTConfig
    from repro.core.plan import plan

    def automl(c):
        return AutoMLConfig(n_trials=c["n_trials"], rungs=tuple(c["rungs"]),
                            keep_frac=c["keep_frac"], val_frac=c["val_frac"],
                            seed=c["seed"])

    return plan(plan_cfg["strategy"], cfg=GenDSTConfig(**plan_cfg["gen_dst"]),
                n=plan_cfg["dst_rows"], m=plan_cfg["dst_cols"],
                sub_automl=automl(plan_cfg["sub_automl"]),
                ft_automl=automl(plan_cfg["fine_tune"]))


def _spec(spec) -> dict:
    return {"preproc": spec.preproc, "feature_frac": float(spec.feature_frac),
            "family": spec.family, "hp": [[k, v] for k, v in spec.hp]}


def _lists(tree):
    """A pytree of arrays as nested dicts and lists of numbers."""
    if isinstance(tree, dict):
        return {k: _lists(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_lists(v) for v in tree]
    return np.asarray(tree, np.float64).tolist()


def summarize(res) -> dict:
    """What a tenant received, reduced to what the reference checks."""
    def automl(r):
        return {"spec": _spec(r.spec), "val_acc": float(r.val_acc),
                "test_acc": None if r.test_acc is None else float(r.test_acc),
                "trials": [[_spec(s), float(v)] for s, v in r.trials],
                "params": _lists(r.params)}

    return {"dst_fitness": float(np.asarray(res.dst_fitness)),
            "row_idx": np.asarray(res.row_idx).astype(int).tolist(),
            "col_idx": np.asarray(res.col_idx).astype(int).tolist(),
            "intermediate": automl(res.intermediate),
            "final": automl(res.final)}


class Tables:
    """Catalog entries' tables, made once and kept."""

    def __init__(self, shape: dict):
        self.shape = shape
        self._made: dict = {}
        self._lock = threading.Lock()

    def get(self, entry: dict):
        with self._lock:
            cell = self._made.setdefault(entry["entry"], {})
        with cell.setdefault("lock", threading.Lock()):
            if "data" not in cell:
                cell["data"] = _tables.job_tables(self.shape, entry["table"])
        return cell["data"]


class Tenant:
    """One tenant: a closed loop over its share of the traffic."""

    def __init__(self, c, client_cls, spec, made, records):
        self.c, self.client_cls = c, client_cls
        self.cfg, self.mix, self.run_seed = (spec["config"], spec["traffic"],
                                             spec["seed"])
        self.made, self.records = made, records
        self.plan = build_plan(self.cfg["plan"])
        self.clients = self.cfg["clients"]
        self.warm = _traffic.warmup_entries(self.mix, c, self.clients)

    def entry(self, k: int) -> dict:
        return _traffic.window_entry(self.mix, self.run_seed, self.c, k,
                                     self.clients)

    def job(self, client, stage: str, k: int, entry: dict) -> dict:
        import jax
        X, y, Xte, yte = self.made.get(entry)
        rec = {"client": self.c, "k": k, "stage": stage,
               "entry": entry["entry"], "table": entry["table"],
               "key_seed": entry["key"]}
        rec["t_submit"] = time.monotonic()
        try:
            jid = client.submit(X, y, tenant=f"t{self.c}",
                                key=jax.random.key(entry["key"]),
                                plan=self.plan, X_test=Xte, y_test=yte)
            rec["t_submitted"] = time.monotonic()
            rec["wall_submitted"] = time.time()
            rec["job_id"] = jid
            res = client.result(jid, timeout_s=RESULT_TIMEOUT_S)
            rec["t_done"] = time.monotonic()
            rec["status"] = "done"
            rec["result"] = summarize(res)
        except Exception as e:   # noqa: BLE001 — a failed job is a record
            rec.setdefault("t_done", time.monotonic())
            rec["status"] = "failed"
            rec["error"] = repr(e)[:500]
        return rec

    def window(self, url: str, t_end: float) -> None:
        """Serve the window sequence from its start until ``t_end``."""
        client = self.client_cls(url, timeout_s=RESULT_TIMEOUT_S)
        k = 0
        while time.monotonic() < t_end:
            self.records.append(self.job(client, "window", k, self.entry(k)))
            k += 1

    def warmup(self, url: str) -> None:
        client = self.client_cls(url, timeout_s=RESULT_TIMEOUT_S)
        for k, entry in enumerate(self.warm):
            self.records.append(self.job(client, "warmup", k, entry))


def _all(tenants, fn) -> list:
    """Run ``fn(tenant)`` on every tenant at once; their results in order."""
    out, errors = [None] * len(tenants), []

    def one(i, t):
        try:
            out[i] = fn(t)
        except Exception:   # noqa: BLE001
            errors.append(traceback.format_exc())

    threads = [threading.Thread(target=one, args=(i, t), daemon=True)
               for i, t in enumerate(tenants)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise RuntimeError(errors[0])
    return out


def main(spec_path: str) -> int:
    spec = json.loads(Path(spec_path).read_text())
    from repro.service import SubStratHTTPClient
    cfg = spec["config"]
    made = Tables(cfg["table"])
    records: list = []
    tenants = [Tenant(c, SubStratHTTPClient, spec, made, records)
               for c in range(cfg["clients"])]
    threading.Thread(target=lambda: [made.get(e) for t in tenants
                                      for e in t.warm],
                     daemon=True).start()
    for line in sys.stdin:
        word, *args = line.split()
        if word == "WARMUP":
            _all(tenants, lambda t: t.warmup(args[0]))
            print("READY", flush=True)
        elif word == "GO":
            t_end = float(args[1])
            _all(tenants, lambda t: t.window(args[0], t_end))
            Path(spec["out"]).write_text(json.dumps({"records": records}))
            print("DONE", flush=True)
            return 0
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
