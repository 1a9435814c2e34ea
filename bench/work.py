"""Operations and bytes a Gen-DST search needs, from its shapes alone.

The least work any implementation of the search does, whichever backend
(``jnp``, ``pallas``, ``pallas_fused``) runs it: the count depends on the
table and the search budget, never on how the work is done.  A roofline
share divides the time this work needs at the chip's peaks by the time the
search took on the device.

One search over ``N`` rows and ``M`` columns (label included), histogram
width ``B``, subset ``n`` rows, population ``phi``, ``psi`` generations:

- the table's own entropy: every code read once (``N*M`` reads and
  histogram adds) and one pass over the ``M*B`` histogram (5 operations a
  cell: normalize, log, multiply, test, add);
- the initial population and each generation's children (crossover runs
  every generation, so each child's histogram is built anew): ``phi``
  candidates each gather ``n*M`` codes and add them up, then reduce their
  ``M*B`` histogram to a fitness.

Bytes count each code read once at 4 bytes (int32); the candidates'
histograms are assumed to stay on chip.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["gen_dst_work", "peaks_for", "roofline_share"]

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
CODE_BYTES = 4


def gen_dst_work(N: int, M: int, B: int, n: int, psi: int, phi: int,
                 **_ignored) -> tuple[float, float]:
    """``(operations, bytes)`` of one Gen-DST search.  Extra keywords
    (the backend among them) are accepted and ignored: they change how the
    work is done, not how much is needed."""
    entropy = 5.0 * M * B
    table_ops = N * M + entropy
    candidates = (psi + 1) * phi
    ops = table_ops + candidates * (n * M + entropy)
    nbytes = CODE_BYTES * (N * M + candidates * n * M)
    return float(ops), float(nbytes)


def peaks_for(device_kind: str) -> dict:
    """The chip's peaks; a device not in ``peaks.json`` is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_share(ops: float, nbytes: float, device_s: float,
                   peaks: dict) -> tuple[float, str]:
    """``(percent of roofline, bound)``: the least time the chip needs for
    the work, at whichever peak bounds it, over the time it took."""
    t_ops = ops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_ops else "compute"
    return 100.0 * max(t_ops, t_bytes) / device_s, bound
