"""Dataset measures for measure-preserving data subsets (SubStrat §3.1).

The paper's primary measure is *dataset entropy* (Def. 3.4): the mean, over
columns, of the Shannon entropy (log2) of each column's empirical value
distribution.  (The formula as printed in the paper is notationally sloppy;
the worked Example 3.5 pins the intended semantics to standard per-column
Shannon entropy, which we match to 3 decimal places in tests.)

All entropy computation operates on *factorized* datasets: every column is
mapped once, up front, to dense integer codes in ``[0, n_bins_j)``.
Categorical / discrete columns keep exact value identity (paper-faithful);
continuous columns are quantile-binned to at most ``max_bins`` codes (see
DESIGN.md §5.1 — Def. 3.4 is degenerate on unrepeated floats).

Layout conventions (the ONE authoritative statement — every ``B``/histogram
docstring in this repo defers here)
---------------------------------------------------------------------------
``codes``   : (N, M) int32 — per-cell code, column j's codes in
              ``[0, n_bins[j])``.
``n_bins``  : (M,)  int32 — number of distinct codes per column.
``B``       : static int — shared histogram width, ``B >= max(n_bins)``.
              Histograms are (M, B) with one row per column.  Bins
              ``b >= n_bins[j]`` are *padding*: no code ever lands there, so
              their count is exactly zero, they carry zero probability mass,
              and they contribute 0 to every entropy sum.  This is what lets
              all M columns (and, in Gen-DST, all candidates) share one
              fixed-shape histogram tensor regardless of per-column
              cardinality.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.jaxprof import note_trace

__all__ = [
    "CodedDataset",
    "factorize",
    "factorize_path",
    "row_bucket",
    "column_counts",
    "column_entropy_from_counts",
    "column_entropy",
    "sorted_entropy",
    "dataset_entropy",
    "subset_counts",
    "subset_entropy",
    "full_column_entropy",
    "measure_pnorm",
    "measure_mean_correlation",
    "measure_coeff_variation",
    "MEASURES",
]


class CodedDataset(NamedTuple):
    """A factorized dataset ready for entropy computation.

    ``values`` keeps the raw (float) matrix for measures other than entropy
    and for downstream AutoML training; ``codes`` drives the entropy measure.
    """

    codes: jax.Array          # (N, M) int32
    values: jax.Array         # (N, M) float32 (raw, un-normalized)
    n_bins: jax.Array         # (M,) int32
    target_col: int           # index of the target column (always in DSTs)
    max_bins: int             # histogram width B (see module docstring)

    @property
    def num_rows(self) -> int:
        return self.codes.shape[0]

    @property
    def num_cols(self) -> int:
        return self.codes.shape[1]


def factorize(
    X: np.ndarray,
    y: Optional[np.ndarray] = None,
    *,
    max_bins: int = 256,
    categorical_threshold: int = 64,
) -> CodedDataset:
    """Factorize a raw matrix (optionally with a target column) to codes.

    Columns with <= ``categorical_threshold`` distinct values keep exact value
    identity (one code per distinct value).  Denser columns are quantile-
    binned to ``max_bins`` codes.  The target column ``y`` (if given) is
    appended as the last column and is always treated as categorical.
    """
    return factorize_path(X, y, max_bins=max_bins,
                          categorical_threshold=categorical_threshold)[0]


def factorize_path(
    X: np.ndarray,
    y: Optional[np.ndarray] = None,
    *,
    max_bins: int = 256,
    categorical_threshold: int = 64,
) -> Tuple[CodedDataset, str]:
    """``factorize``, and the path that coded the table: ``"device"`` or
    ``"host"`` (DESIGN.md §5.1).  Both give bit-identical results; the
    device path is taken whenever the input is finite and exactly
    representable in float32 and ``max_bins`` is a power of two."""
    X = np.asarray(X)
    y = None if y is None else np.asarray(y)
    coded = _factorize_device(X, y, max_bins, categorical_threshold)
    if coded is not None:
        return coded, "device"
    return _factorize_host(X, y, max_bins, categorical_threshold), "host"


def _factorize_host(X: np.ndarray, y: Optional[np.ndarray], max_bins: int,
                    categorical_threshold: int) -> CodedDataset:
    """The reference semantics, in numpy over float64 columns."""
    cols = [np.asarray(X[:, j]) for j in range(X.shape[1])]
    if y is not None:
        cols.append(np.asarray(y))
    N = X.shape[0]
    codes = np.empty((N, len(cols)), dtype=np.int32)
    n_bins = np.empty((len(cols),), dtype=np.int32)
    values = np.empty((N, len(cols)), dtype=np.float32)
    for j, col in enumerate(cols):
        colf = col.astype(np.float64)
        values[:, j] = colf.astype(np.float32)
        uniq, inv = np.unique(colf, return_inverse=True)
        if len(uniq) <= max(categorical_threshold, 2) or (
            y is not None and j == len(cols) - 1
        ):
            codes[:, j] = inv.astype(np.int32)
            n_bins[j] = len(uniq)
        else:
            # quantile binning to at most max_bins codes
            qs = np.quantile(colf, np.linspace(0.0, 1.0, max_bins + 1)[1:-1])
            binned = np.searchsorted(qs, colf, side="right")
            # re-densify (some quantile bins may be empty)
            uniq_b, inv_b = np.unique(binned, return_inverse=True)
            codes[:, j] = inv_b.astype(np.int32)
            n_bins[j] = len(uniq_b)
    B = int(max(int(n_bins.max()), 2))
    return CodedDataset(
        codes=jnp.asarray(codes),
        values=jnp.asarray(values),
        n_bins=jnp.asarray(n_bins),
        target_col=len(cols) - 1 if y is not None else X.shape[1] - 1,
        max_bins=B,
    )


# ---------------------------------------------------------------------------
# Device factorize (DESIGN.md §5.1): one sort per column on the chip.
# ---------------------------------------------------------------------------

ROW_FLOOR = 4096        # the smallest row bucket of the sort program
CHUNK_COLS = 8          # columns per call of the sort program
_F32_EXACT_INT = 2 ** 24


def row_bucket(n: int) -> int:
    """Rows the sort program is compiled for when a table has ``n``: the
    least ``2**e * {1, 1.25, 1.5, 1.75}`` that holds them, at least
    ``ROW_FLOOR`` — so at most 4 programs per doubling of the row count."""
    if n <= ROW_FLOOR:
        return ROW_FLOOR
    step = 1 << ((n - 1).bit_length() - 3)
    return -(-n // step) * step


def _exact_f32(a: np.ndarray) -> Optional[np.ndarray]:
    """``a`` as float32 if every value converts exactly, else None (NaN
    never compares exact; infinities are caught on the device)."""
    if a.dtype == np.float32:
        return a
    if a.dtype.kind in "iu":
        if a.size and (a.min() < -_F32_EXACT_INT or a.max() > _F32_EXACT_INT):
            return None
        return a.astype(np.float32)
    if a.dtype.kind == "f":
        a32 = a.astype(np.float32)
        return a32 if np.array_equal(a32, a) else None
    return None


def _factorize_device(X: np.ndarray, y: Optional[np.ndarray], max_bins: int,
                      categorical_threshold: int) -> Optional[CodedDataset]:
    """The device path, or None where it cannot represent the input
    exactly (DESIGN.md §5.1)."""
    N = X.shape[0]
    if (X.ndim != 2 or N == 0 or max_bins < 2 or max_bins & (max_bins - 1)
            or (y is not None and y.shape != (N,))):
        return None
    X32 = _exact_f32(X)
    y32 = None if y is None else _exact_f32(y)
    if X32 is None or (y is not None and y32 is None):
        return None
    chunks, values, finite = _column_chunks(
        jax.device_put(X32), None if y32 is None else jax.device_put(y32),
        npad=row_bucket(N))
    M = values.shape[1]
    label = M - 1 if y is not None else -1
    outs = [_factorize_chunk(c, N, label - i * CHUNK_COLS,
                             categorical_threshold, max_bins=max_bins)
            for i, c in enumerate(chunks)]
    codes = _codes_matrix(tuple(c for c, _ in outs), n=N, m=M)
    codes.copy_to_host_async()     # the fingerprint reads them next
    ok, *n_bins = jax.device_get([finite] + [nb for _, nb in outs])
    if not ok:
        return None
    n_bins = np.concatenate(n_bins)[:M]
    return CodedDataset(
        codes=codes,
        values=values,
        n_bins=jnp.asarray(n_bins),
        target_col=M - 1,
        max_bins=int(max(int(n_bins.max()), 2)),
    )


@functools.partial(jax.jit, static_argnames=("npad",))
def _column_chunks(X, y, npad: int):
    """The uploaded table as ``values`` (N, M), its finiteness, and its
    columns in chunks of ``CHUNK_COLS`` rows of ``npad``, padded with +inf
    (padding sorts after every finite value)."""
    note_trace("measures.factorize_columns")
    values = X if y is None else jnp.concatenate([X, y[:, None]], axis=1)
    N, M = values.shape
    nch = -(-M // CHUNK_COLS)
    cols = jnp.pad(values.T, ((0, nch * CHUNK_COLS - M), (0, npad - N)),
                   constant_values=jnp.inf)
    chunks = tuple(cols[i * CHUNK_COLS:(i + 1) * CHUNK_COLS]
                   for i in range(nch))
    return chunks, values, jnp.isfinite(values).all()


@functools.partial(jax.jit, static_argnames=("max_bins",))
def _factorize_chunk(cols, n, label, categorical_threshold, *,
                     max_bins: int):
    """Codes (in row order) and ``n_bins`` of a chunk of columns.

    cols: (C, npad) float32 whose first ``n`` entries of each row are the
    column, the rest padding; ``label`` is the chunk-local index of the
    target column (always categorical), out of range if none.  The program
    depends on ``(C, npad, max_bins)`` only.
    """
    note_trace("measures.factorize_device")
    C, npad = cols.shape
    pos = jnp.broadcast_to(jnp.arange(npad, dtype=jnp.int32), (C, npad))
    s, perm = jax.lax.sort((cols, pos), dimension=1, num_keys=1)

    def dense(v):
        """Dense rank of each entry of a row-wise sorted ``v``."""
        step = jnp.concatenate(
            [jnp.zeros((C, 1), jnp.int32),
             (v[:, 1:] != v[:, :-1]).astype(jnp.int32)], axis=1)
        return jnp.cumsum(step, axis=1, dtype=jnp.int32)

    rank = dense(s)                            # categorical code, sorted
    # np.quantile's threshold k sits at virtual index k(n-1)/max_bins; it is
    # the value at position lo when that index is whole, else it lies
    # strictly between positions lo and lo+1, where no value lies, so it
    # splits the column exactly where position lo+1 does (DESIGN.md §5.1)
    k = jnp.arange(1, max_bins, dtype=jnp.int32)
    q, r = jnp.divmod(n - 1, max_bins)
    t = k * q + (k * r) // max_bins + ((k * r) % max_bins != 0)
    edges = rank[:, t]                          # (C, max_bins - 1), sorted
    # compare_all: on a TPU v5e the default binary search's gathers take
    # 59 ms per chunk of 114,688 rows, the full comparison 0.7 ms
    binned = jax.vmap(lambda e, rk: jnp.searchsorted(
        e, rk, side="right", method="compare_all"))(edges, rank)
    bin_code = dense(binned)                    # re-densified bins, sorted

    is_cat = ((rank[:, n - 1] + 1 <= jnp.maximum(categorical_threshold, 2))
              | (jnp.arange(C) == label))
    code = jnp.where(is_cat[:, None], rank, bin_code)
    n_bins = code[:, n - 1] + 1
    _, codes = jax.lax.sort((perm, code), dimension=1, num_keys=1)
    return codes, n_bins


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _codes_matrix(chunks, n: int, m: int):
    """The chunks' codes as the (n, m) matrix of ``CodedDataset.codes``."""
    note_trace("measures.factorize_codes")
    return jnp.concatenate(chunks, axis=0)[:m, :n].T


# ---------------------------------------------------------------------------
# Histogram + entropy primitives (pure jnp; the Pallas kernel in
# repro/kernels/entropy mirrors subset_counts' masked-histogram semantics).
# ---------------------------------------------------------------------------


def column_counts(codes: jax.Array, B: int, weights: Optional[jax.Array] = None) -> jax.Array:
    """Per-column histogram via flat scatter-add.

    codes: (n, M) int32;  weights: optional (n,) f32 row weights.
    Returns (M, B) float32 counts.
    """
    n, M = codes.shape
    flat = (codes + jnp.arange(M, dtype=codes.dtype)[None, :] * B).ravel()
    w = jnp.ones((n,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    w = jnp.broadcast_to(w[:, None], (n, M)).ravel()
    counts = jnp.zeros((M * B,), jnp.float32).at[flat].add(w)
    return counts.reshape(M, B)


def column_entropy_from_counts(counts: jax.Array) -> jax.Array:
    """Shannon entropy (log2) per column from (M, B) counts. Zero-safe."""
    total = jnp.maximum(counts.sum(axis=-1, keepdims=True), 1e-12)
    p = counts / total
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-30)), 0.0), axis=-1)
    return h  # (M,)


def column_entropy(codes: jax.Array, B: int, weights: Optional[jax.Array] = None) -> jax.Array:
    return column_entropy_from_counts(column_counts(codes, B, weights))


def dataset_entropy(
    codes: jax.Array,
    B: int,
    col_mask: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
) -> jax.Array:
    """H(D) (Def. 3.4): mean over (selected) columns of column entropy."""
    h = column_entropy(codes, B, weights)
    if col_mask is None:
        return h.mean()
    cm = col_mask.astype(jnp.float32)
    return jnp.sum(h * cm) / jnp.maximum(cm.sum(), 1.0)


def sorted_entropy(samples: jax.Array) -> jax.Array:
    """Shannon entropy (log2) of each row's empirical distribution, from
    one sort of the row: no histogram and no scatter.

    samples: (..., n) int32 codes, one sample per entry of the last axis.
    Returns (...,) float32.  Each run of equal codes in the sorted row is
    one nonempty bin of ``column_counts``: its length, found from the run's
    start by a running max, is placed at the run's end and zeros elsewhere,
    and ``column_entropy_from_counts`` reads that length-``n`` vector.  So
    every term is the histogram path's, computed the same way; only the
    order of summation differs (DESIGN.md §16.3).
    """
    n = samples.shape[-1]
    s = jnp.sort(samples, axis=-1)
    i = jnp.arange(n, dtype=jnp.int32)
    step = s[..., 1:] != s[..., :-1]
    edge = jnp.ones(s.shape[:-1] + (1,), bool)
    first = jnp.concatenate([edge, step], axis=-1)
    last = jnp.concatenate([step, edge], axis=-1)
    start = jax.lax.cummax(jnp.where(first, i, 0), axis=s.ndim - 1)
    runs = jnp.where(last, (i - start + 1).astype(jnp.float32), 0.0)
    return column_entropy_from_counts(runs)


@functools.partial(jax.jit, static_argnames=("B", "chunk"))
def full_column_entropy(codes: jax.Array, B: int, chunk: int = 64) -> jax.Array:
    """Column entropy of the full dataset: one sort per column, at most
    ``chunk`` columns at a time (bounded memory).

    Used once per Gen-DST run to precompute the reference ``F(D)`` terms.
    ``B`` is the histogram width the other entropy helpers take; the sort
    needs none.
    """
    note_trace("measures.full_column_entropy")   # body runs only at trace
    N, M = codes.shape
    steps = -(-M // chunk)
    width = -(-M // steps)                        # pads fewer than ``steps``
    cols = jnp.pad(codes.T, ((0, steps * width - M), (0, 0)))  # constant cols
    h = jax.lax.map(sorted_entropy, cols.reshape(steps, width, N))
    return h.reshape(-1)[:M]


def subset_counts(codes: jax.Array, row_idx: jax.Array, B: int) -> jax.Array:
    """Histogram of the rows indexed by ``row_idx`` (gather path; single host).

    codes: (N, M); row_idx: (n,) int32. Returns (M, B) counts.
    """
    sub = jnp.take(codes, row_idx, axis=0)  # (n, M)
    return column_counts(sub, B)


def subset_entropy(
    codes: jax.Array,
    row_idx: jax.Array,
    col_mask: jax.Array,
    B: int,
) -> jax.Array:
    """H(D[r, c]) for one candidate DST: rows by index, columns by mask."""
    h = column_entropy_from_counts(subset_counts(codes, row_idx, B))  # (M,)
    cm = col_mask.astype(jnp.float32)
    return jnp.sum(h * cm) / jnp.maximum(cm.sum(), 1.0)


# ---------------------------------------------------------------------------
# Alternative dataset measures (paper §3.1: "other possible dataset measures
# ... p-norm, mean-correlation, and coefficient of variation").  These run on
# the raw float values of the subset.
# ---------------------------------------------------------------------------


def _subset_values(values: jax.Array, row_idx: jax.Array,
                   col_mask: Optional[jax.Array]):
    sub = jnp.take(values, row_idx, axis=0)  # (n, M)
    # registry contract: col_mask=None means "all columns" — every measure
    # must accept fn(values, row_idx) without a mask
    cm = (jnp.ones((values.shape[1],), jnp.float32) if col_mask is None
          else col_mask.astype(jnp.float32))
    return sub, cm


def measure_pnorm(values, row_idx=None, col_mask=None, p: float = 2.0):
    """Mean per-column p-norm, normalized by row count (scale-comparable)."""
    if row_idx is None:
        sub = values
        cm = jnp.ones((values.shape[1],), jnp.float32) if col_mask is None else col_mask.astype(jnp.float32)
    else:
        sub, cm = _subset_values(values, row_idx, col_mask)
    n = sub.shape[0]
    norms = (jnp.sum(jnp.abs(sub) ** p, axis=0) / n) ** (1.0 / p)  # (M,)
    return jnp.sum(norms * cm) / jnp.maximum(cm.sum(), 1.0)


def measure_mean_correlation(values, row_idx=None, col_mask=None):
    """Mean absolute pairwise Pearson correlation among selected columns."""
    if row_idx is None:
        sub = values
        cm = jnp.ones((values.shape[1],), jnp.float32) if col_mask is None else col_mask.astype(jnp.float32)
    else:
        sub, cm = _subset_values(values, row_idx, col_mask)
    mu = sub.mean(axis=0, keepdims=True)
    sd = sub.std(axis=0, keepdims=True) + 1e-9
    z = (sub - mu) / sd
    corr = (z.T @ z) / sub.shape[0]  # (M, M)
    w = cm[:, None] * cm[None, :]
    w = w * (1.0 - jnp.eye(values.shape[1]))
    return jnp.sum(jnp.abs(corr) * w) / jnp.maximum(w.sum(), 1.0)


def measure_coeff_variation(values, row_idx=None, col_mask=None):
    """Mean per-column coefficient of variation sigma/|mu|."""
    if row_idx is None:
        sub = values
        cm = jnp.ones((values.shape[1],), jnp.float32) if col_mask is None else col_mask.astype(jnp.float32)
    else:
        sub, cm = _subset_values(values, row_idx, col_mask)
    mu = sub.mean(axis=0)
    sd = sub.std(axis=0)
    cv = sd / (jnp.abs(mu) + 1e-9)
    return jnp.sum(cv * cm) / jnp.maximum(cm.sum(), 1.0)


# Registry contract: ``MEASURES[name]`` is either
#   * a callable ``fn(values, row_idx=None, col_mask=None) -> scalar`` that
#     scores a (sub)dataset on raw float values — Gen-DST evaluates it per
#     candidate with ``fn(values, rows, col_mask)`` and the reference value
#     as ``fn(values)``; or
#   * ``None`` for "entropy", which is NOT computed through this generic
#     interface: entropy works on factorized codes, so Gen-DST routes it
#     through the histogram fast path (carried per-candidate counts +
#     kernels/entropy backends) instead of a values-based callable.  Code
#     dispatching on a measure name must special-case ``"entropy"`` before
#     indexing this dict.
MEASURES = {
    "entropy": None,
    "pnorm": measure_pnorm,
    "mean_correlation": measure_mean_correlation,
    "coeff_variation": measure_coeff_variation,
}
