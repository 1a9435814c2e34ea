"""Gen-DST work counts and the table of peaks."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import work  # noqa: E402

D1 = json.loads((BENCH / "configs" / "d1-airline.json").read_text())


def _work(backend):
    cfg = dict(D1["plan"]["gen_dst"], backend=backend)
    return work.gen_dst_work(N=103904, M=23, B=256, n=322, **cfg)


def test_work_is_the_same_for_every_backend():
    assert _work("jnp") == _work("pallas") == _work("pallas_fused")


def test_work_counts_from_shapes():
    ops, nbytes = work.gen_dst_work(N=10, M=3, B=4, n=2, psi=1, phi=2)
    entropy = 5 * 3 * 4
    assert ops == 10 * 3 + entropy + (1 + 1) * 2 * (2 * 3 + entropy)
    assert nbytes == 4 * (10 * 3 + (1 + 1) * 2 * 2 * 3)


def test_v5e_peaks_and_roofline():
    peaks = work.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    share, bound = work.roofline_share(0.0, 819e9, 2.0, peaks)
    assert (share, bound) == (pytest.approx(50.0), "memory")
    share, bound = work.roofline_share(197e12, 0.0, 4.0, peaks)
    assert (share, bound) == (pytest.approx(25.0), "compute")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
