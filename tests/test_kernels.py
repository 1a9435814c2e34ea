"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps +
hypothesis properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal env — deterministic fallback, same API subset
    from _hyp_fallback import given, settings, strategies as st

from repro.kernels.entropy.kernel import masked_histogram_pallas
from repro.kernels.entropy.ref import masked_histogram_ref, entropy_from_hist
from repro.kernels.entropy.ops import column_entropy_masked
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


# ---------------------------------------------------------------------------
# entropy / masked histogram
# ---------------------------------------------------------------------------

ENTROPY_SHAPES = [
    (16, 1, 2), (100, 5, 7), (1000, 23, 256), (513, 3, 16),
    (2048, 8, 64), (77, 123, 11),
]


@pytest.mark.parametrize("N,M,B", ENTROPY_SHAPES)
def test_entropy_kernel_matches_ref(N, M, B):
    rng = np.random.default_rng(N * 31 + M)
    codes = jnp.asarray(rng.integers(0, B, (N, M)), jnp.int32)
    w = jnp.asarray((rng.random(N) < 0.4).astype(np.float32))
    h_k = masked_histogram_pallas(codes, w, B)
    h_r = masked_histogram_ref(codes, w, B)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-4)


@pytest.mark.parametrize("wdtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_entropy_kernel_weight_dtypes(wdtype):
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 16, (256, 4)), jnp.int32)
    w = jnp.asarray(rng.integers(0, 2, 256), wdtype)
    h_k = masked_histogram_pallas(codes, w.astype(jnp.float32), 16)
    h_r = masked_histogram_ref(codes, w.astype(jnp.float32), 16)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-4)


@pytest.mark.parametrize("tile_n,tile_m", [(8, 128), (64, 128), (1024, 256)])
def test_entropy_kernel_tile_sweep(tile_n, tile_m):
    rng = np.random.default_rng(7)
    codes = jnp.asarray(rng.integers(0, 32, (500, 9)), jnp.int32)
    w = jnp.asarray(rng.random(500), jnp.float32)
    h_k = masked_histogram_pallas(codes, w, 32, tile_n=tile_n, tile_m=tile_m)
    h_r = masked_histogram_ref(codes, w, 32)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 200), st.integers(1, 6), st.integers(2, 32), st.integers(0, 99))
def test_entropy_kernel_property(N, M, B, seed):
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, B, (N, M)), jnp.int32)
    w = jnp.asarray(rng.random(N), jnp.float32)
    h_k = masked_histogram_pallas(codes, w, B)
    # mass conservation: every column's histogram sums to sum(w)
    np.testing.assert_allclose(np.asarray(h_k.sum(axis=1)),
                               float(w.sum()) * np.ones(M), rtol=1e-4)
    h_r = masked_histogram_ref(codes, w, B)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-3)


@pytest.mark.parametrize("tile_n,tile_m", [(4, 128), (8, 64), (12, 256)])
def test_entropy_kernel_rejects_unaligned_tiles(tile_n, tile_m):
    codes = jnp.zeros((16, 4), jnp.int32)
    with pytest.raises(ValueError, match="multiple of"):
        masked_histogram_pallas(codes, jnp.ones((16,)), 4,
                                tile_n=tile_n, tile_m=tile_m)


# padding edges (DESIGN.md §16.4): rows shorter than one tile, a ragged
# column tile, bins beyond every observed code (B > max(n_bins)), and the
# (8, 128) tile edges themselves must match the jnp oracle bit for bit —
# padding rows carry zero weight, padding columns and bins are sliced off.
# Weights are a 0/1 membership mask, as Gen-DST uses them, so every count is
# an exact small integer whatever the order of the adds.
PADDING_EDGE_SHAPES = [
    # (N, M, B, code_max)
    (5, 3, 8, None),        # N=5 < tile_n — one mostly-padded row tile
    (300, 13, 16, None),    # M=13 < tile_m=128 — ragged column tile
    (200, 4, 64, 11),       # codes < 11 << B=64 — padding bins
    (7, 9, 32, 5),          # all three edges at once
    (8, 128, 128, None),    # exactly one (8, 128) tile, B = one lane width
    (9, 129, 129, None),    # one past the tile on every axis
    (16, 256, 256, 200),    # two column tiles, B = 256 with padding bins
    (24, 2300, 256, None),  # the D1 population fold: P * M = 100 * 23
]


def _padding_case(N, M, B, code_max):
    rng = np.random.default_rng(N * 7 + M)
    hi = B if code_max is None else code_max
    codes = jnp.asarray(rng.integers(0, hi, (N, M)), jnp.int32)
    w = jnp.asarray((rng.random(N) < 0.6).astype(np.float32))
    return codes, w


@pytest.mark.parametrize("N,M,B,code_max", PADDING_EDGE_SHAPES)
def test_histogram_padding_edges_interpret(N, M, B, code_max):
    codes, w = _padding_case(N, M, B, code_max)
    h_k = masked_histogram_pallas(codes, w, B, interpret=True)
    h_r = masked_histogram_ref(codes, w, B)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
    if code_max is not None:  # bins no code can reach must stay empty
        assert not np.asarray(h_k)[:, code_max:].any()


def test_resolve_interpret_interprets_only_on_cpu(monkeypatch):
    from repro.kernels import resolve_interpret
    assert resolve_interpret(None) is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True      # an explicit choice wins
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False


def test_column_entropy_masked_matches_measures():
    from repro.core.measures import column_entropy
    rng = np.random.default_rng(3)
    codes = jnp.asarray(rng.integers(0, 8, (300, 5)), jnp.int32)
    mask = jnp.asarray((rng.random(300) < 0.5).astype(np.float32))
    h1 = column_entropy_masked(codes, mask, 8, use_pallas=True)
    h2 = column_entropy(codes, 8, weights=mask)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # (B, Sq, Skv, H, K, hd, causal, dtype)
    (2, 128, 128, 4, 2, 64, True, jnp.float32),
    (1, 256, 256, 8, 8, 32, True, jnp.float32),
    (2, 128, 128, 4, 1, 128, False, jnp.float32),
    (1, 128, 128, 4, 4, 256, True, jnp.float32),
    (2, 128, 128, 8, 2, 64, True, jnp.bfloat16),
]


@pytest.mark.parametrize("B,Sq,Skv,H,K,hd,causal,dtype", FA_CASES)
def test_flash_attention_matches_ref(B, Sq, Skv, H, K, hd, causal, dtype):
    rng = np.random.default_rng(Sq + H)
    q = jnp.asarray(rng.normal(0, 1, (B, Sq, H, hd)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (B, Skv, K, hd)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (B, Skv, K, hd)), dtype)
    o_k = flash_attention_pallas(q, k, v, causal=causal, block_q=64, block_k=64)
    o_r = attention_ref(q, k, v, causal=causal)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32), atol=atol
    )


@pytest.mark.parametrize("block_q,block_k", [(32, 64), (64, 32), (128, 128)])
def test_flash_attention_block_sweep(block_q, block_k):
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(0, 1, (1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 128, 2, 32)), jnp.float32)
    o_k = flash_attention_pallas(q, k, v, causal=True,
                                 block_q=block_q, block_k=block_k)
    o_r = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5)


def test_flash_attention_softmax_rows_normalized():
    """Causal row 0 attends only to key 0 => output == v[0]."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (1, 64, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 64, 2, 16)), jnp.float32)
    o = flash_attention_pallas(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(o[0, 0]), np.asarray(v[0, 0]), atol=1e-5)
