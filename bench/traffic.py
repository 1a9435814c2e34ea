"""The one traffic generator: which table each job of a mix sends.

A traffic mix is a data file, ``bench/traffic/<name>.json``, read by the
functions here; a new mix is a new file.  Every mix draws its tables from a
fixed catalog of ``catalog`` tables of the configuration's shape.  Entry
``i`` of the catalog is a content seed and a PRNG key seed that depend on
``i`` alone, so the programs a job compiles are the same in every run and
the persistent compilation cache holds them after a checkout's first runs.

Set-up submits every catalog table once, so that the programs each table's
contents call for are compiled, or loaded from that cache, before the window
opens.  In the window:

- ``"tables": "fresh"`` — the tenants cycle through the catalog in its
  order, the same in every run.  The mix's ``server`` settings give the
  window's server a DST cache of one entry, so a table that comes round
  again is not found there, as a new table would not be: every job runs
  Gen-DST, sub-AutoML and fine-tune.
- ``"tables": "pool"`` — the catalog is a pool of recurring tables; entry
  ``i`` is drawn with the weight ``(i + 1) ** -zipf_s`` (a Zipf law).  The
  sequence is cut into blocks of ``block`` jobs; each block holds every
  entry in proportion to its weight, rounded to whole jobs, in an order the
  run's ``--seed`` shuffles, so every seed sends the same work.  Every job
  finds its subset and its winner family in the server's cache.

Several tenants deal the sequence round: job ``k`` of tenant ``c`` of ``n``
is place ``c + k*n``.  Nothing depends on the timing.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from tables import table_seed

__all__ = ["load", "catalog_entry", "window_entry", "warmup_entries",
           "block_entries"]

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
_CATALOG, _KEY, _ORDER = 21, 22, 23


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {name!r} ({path} is missing)")
    mix = json.loads(path.read_text())
    kind = mix.get("tables")
    if kind not in ("fresh", "pool"):
        raise ValueError(f"traffic mix {name!r}: tables must be fresh or pool")
    if int(mix.get("catalog", 0)) < (2 if kind == "fresh" else 1):
        raise ValueError(f"traffic mix {name!r}: catalog too small")
    if kind == "pool" and int(mix.get("block", 0)) < 1:
        raise ValueError(f"traffic mix {name!r}: block must be at least 1")
    return mix


def catalog_entry(i: int) -> dict:
    """Catalog entry ``i``: its content seed and its 32-bit key seed."""
    return {"entry": int(i), "table": table_seed(0, _CATALOG, i),
            "key": table_seed(0, _KEY, i)}


def block_entries(mix: dict, b: int) -> list:
    """The pool entries of block ``b``, before the seed's shuffle."""
    size, block = int(mix["catalog"]), int(mix["block"])
    w = np.arange(1, size + 1, dtype=np.float64) ** -float(mix["zipf_s"])
    quota = w / w.sum() * block
    counts = np.floor(quota).astype(int)
    for i in np.argsort(counts - quota)[: block - counts.sum()]:
        counts[i] += 1             # largest remainders first
    return [i for i in range(size) for _ in range(counts[i])]


def window_entry(mix: dict, run_seed: int, client: int, k: int,
                 clients: int) -> dict:
    """The catalog entry that job ``k`` of tenant ``client`` sends in the
    window."""
    place = client + k * clients
    if mix["tables"] == "fresh":
        return catalog_entry(place % int(mix["catalog"]))
    b, j = divmod(place, int(mix["block"]))
    order = np.random.default_rng(table_seed(run_seed, _ORDER, b))
    entries = block_entries(mix, b)
    return catalog_entry(entries[order.permutation(len(entries))[j]])


def warmup_entries(mix: dict, client: int, clients: int) -> list:
    """The entries tenant ``client`` submits in set-up: each catalog table
    once, dealt round the tenants."""
    return [catalog_entry(i) for i in range(client, int(mix["catalog"]),
                                            clients)]
