"""Fused Gen-DST generation kernel (DESIGN.md §16): parity + padding edges.

Parity contract: the interpret-mode Pallas kernel must match the pure-jnp
oracle bit-for-bit on CPU (identical op sequence on exact integer-valued f32
counts); ``tests/test_tpu_compile.py`` compiles it for the chip at real
widths.  End-to-end, ``backend="pallas_fused"`` must reproduce the
``backend="jnp"`` GA trajectory exactly for the same seed — winner rows,
winner column mask, and fitness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gen_dst import GenDSTConfig, gen_dst, gen_dst_batch
from repro.core.measures import factorize
from repro.kernels.gen_dst.kernel import fused_delta_fitness_pallas
from repro.kernels.gen_dst.ops import fused_delta_fitness
from repro.kernels.gen_dst.ref import fused_delta_fitness_ref


def _case(P, M, B, seed, code_max=None):
    """Random fused-kernel inputs; ``code_max`` < B leaves padding bins."""
    rng = np.random.default_rng(seed)
    hi = B if code_max is None else code_max
    n = 12  # rows per candidate histogram
    base = rng.integers(0, hi, (P, n, M))
    counts = np.zeros((P, M, B), np.float32)
    for p in range(P):
        for j in range(M):
            np.add.at(counts[p, j], base[p, :, j], 1.0)
    old = base[:, 0, :].astype(np.int32)           # evict a real member row
    new = rng.integers(0, hi, (P, M)).astype(np.int32)
    applied = rng.random(P) < 0.6
    col_mask = rng.random((P, M)) < 0.5
    col_mask[:, 0] = True                           # never an empty mask
    f_ref = np.float32(rng.random() * 3.0)
    return (jnp.asarray(counts), jnp.asarray(old), jnp.asarray(new),
            jnp.asarray(applied), jnp.asarray(col_mask), jnp.asarray(f_ref))


# --- kernel-level parity, incl. the padding edges ---------------------------

FUSED_CASES = [
    # (P, M, B, code_max): P < tile_p, P % tile_p != 0, B > max code
    (3, 4, 8, None),       # P=3 < tile_p=8 — single padded candidate tile
    (10, 5, 16, None),     # P=10 % 8 != 0 — ragged last tile
    (16, 3, 32, 17),       # codes < 17 < B=32 — padding bins must stay empty
    (8, 7, 8, None),       # exact tile fit
    (25, 2, 64, 40),       # ragged + padding bins together
]


@pytest.mark.parametrize("P,M,B,code_max", FUSED_CASES)
def test_fused_kernel_matches_ref(P, M, B, code_max):
    args = _case(P, M, B, seed=P * 131 + B, code_max=code_max)
    c_ref, f_ref_out = fused_delta_fitness_ref(*args)
    c_k, f_k = fused_delta_fitness_pallas(*args, bins=B, interpret=True)
    # bit-level oracle: identical op sequence on exact small-integer counts
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref_out))


@pytest.mark.parametrize("P,M,B", [(12, 160, 32), (10, 300, 16)])
def test_fused_kernel_matches_ref_wider_than_a_lane_tile(P, M, B):
    """M > 128 columns in one block: bit-equal, as at every other width."""
    args = _case(P, M, B, seed=M + B)
    c_ref, f_ref_out = fused_delta_fitness_ref(*args)
    c_k, f_k = fused_delta_fitness_pallas(*args, bins=B, interpret=True)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref_out))


def test_fused_kernel_mass_conservation_and_padding_bins():
    """A row swap conserves per-column mass; codes < B leaves the high
    padding bins untouched (all-zero before and after the delta)."""
    P, M, B, code_max = 10, 4, 32, 9
    counts, old, new, applied, cm, fr = _case(P, M, B, seed=5, code_max=code_max)
    c_k, _ = fused_delta_fitness_pallas(
        counts, old, new, applied, cm, fr, bins=B, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(c_k.sum(-1)), np.asarray(counts.sum(-1)))
    assert not np.asarray(c_k)[:, :, code_max:].any()


def test_fused_op_leading_axes_roundtrip():
    """ops.fused_delta_fitness flattens (islands, phi, ...) leading axes and
    restores them; result matches candidate-by-candidate ref calls."""
    counts, old, new, applied, cm, fr = _case(12, 3, 8, seed=9)
    sh = lambda a, tail: a.reshape(2, 6, *tail)
    c2, fit = fused_delta_fitness(
        sh(counts, (3, 8)), sh(old, (3,)), sh(new, (3,)), applied.reshape(2, 6),
        sh(cm, (3,)), fr, backend="pallas_fused", interpret=True)
    assert c2.shape == (2, 6, 3, 8) and fit.shape == (2, 6)
    c_ref, f_ref_out = fused_delta_fitness_ref(counts, old, new, applied, cm, fr)
    np.testing.assert_array_equal(np.asarray(c2).reshape(12, 3, 8), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(fit).reshape(12), np.asarray(f_ref_out))


def test_fused_op_unknown_backend_raises():
    args = _case(4, 2, 4, seed=0)
    with pytest.raises(ValueError, match="unknown fused Gen-DST backend"):
        fused_delta_fitness(*args, backend="cuda")


@pytest.mark.parametrize("backend", ["pallas", "pallas_fused"])
def test_pallas_backend_rejects_non_entropy_measure(coded, backend):
    """The kernels compute entropy histograms only: another measure on a
    Pallas backend is refused instead of quietly running the jnp path."""
    cfg = GenDSTConfig(psi=2, phi=4, measure="pnorm", backend=backend)
    with pytest.raises(ValueError, match="needs measure='entropy'"):
        gen_dst(jax.random.key(0), coded, 16, 3, cfg)


# --- end-to-end GA parity: backend="pallas_fused" vs "jnp" ------------------


@pytest.fixture(scope="module")
def coded():
    rng = np.random.default_rng(42)
    X = np.column_stack([
        rng.integers(0, k, 400) for k in (3, 5, 11, 2, 20)
    ]).astype(float)
    y = rng.integers(0, 2, 400).astype(float)
    return factorize(X, y)


@pytest.mark.parametrize("cross_every,num_islands", [(1, 1), (3, 2)])
def test_fused_backend_same_winner_as_jnp(coded, cross_every, num_islands):
    mk = lambda b: GenDSTConfig(psi=4, phi=8, backend=b,
                                cross_every=cross_every,
                                num_islands=num_islands, migrate_every=2)
    key = jax.random.key(17)
    r_j = gen_dst(key, coded, 16, 3, mk("jnp"))
    r_f = gen_dst(key, coded, 16, 3, mk("pallas_fused"))
    np.testing.assert_array_equal(np.asarray(r_f.row_idx), np.asarray(r_j.row_idx))
    np.testing.assert_array_equal(np.asarray(r_f.col_mask), np.asarray(r_j.col_mask))
    assert abs(float(r_f.fitness) - float(r_j.fitness)) < 1e-5
    np.testing.assert_allclose(np.asarray(r_f.history), np.asarray(r_j.history),
                               atol=1e-5)


def test_fused_backend_batch_matches_solo_jnp(coded):
    cfg_f = GenDSTConfig(psi=4, phi=8, backend="pallas_fused", cross_every=2)
    cfg_j = cfg_f._replace(backend="jnp")
    keys = [jax.random.key(3), jax.random.key(4)]
    batch = gen_dst_batch(keys, [coded, coded], 16, 3, cfg_f)
    for k, res in zip(keys, batch):
        solo = gen_dst(k, coded, 16, 3, cfg_j)
        np.testing.assert_array_equal(np.asarray(res.row_idx),
                                      np.asarray(solo.row_idx))
        assert abs(float(res.fitness) - float(solo.fitness)) < 1e-5
