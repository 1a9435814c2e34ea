"""Gen-DST (SubStrat Algorithm 1) — fully vectorized genetic algorithm in JAX.

Genome representation (DESIGN.md §5.2):
  * rows   : (phi, n) int32 index matrix (a candidate's row subset r).
  * columns: (phi, M) bool membership mask with exactly ``m`` True entries,
             the target column always pinned True (paper §3.3: the target
             column is inserted into every DST and cannot be mutated).

The whole GA — mutation, crossover, royalty-tournament selection, fitness —
runs on device under one ``lax.scan`` over generations: no host round trips.
Fitness is the paper's ``f(G) = -|F(D[r,c]) - F(D)|`` with F = dataset
entropy evaluated via masked histograms (see measures.py / kernels/entropy).

Search-loop architecture (DESIGN.md §5.5):
  * Incremental fitness: each candidate's (M, B) count tensor rides in the
    scan carry.  A row mutation replaces exactly one row, so its histogram
    delta is one subtract + one add of a single row's codes — O(M) scatter
    work instead of an O(n*M) re-gather.  Column mutation/crossover never
    touches counts at all: counts cover all M columns, the column mask only
    reweights the entropy average.  Full recomputes happen only on
    row-crossover generations (``cross_every`` cadence) and route through
    ``kernels/entropy`` under the ``backend=`` switch ("jnp" scatter-add
    reference, or the Pallas histogram kernel).
  * Sort path (``gen_dst_path`` "sort"): with crossover every generation
    (``cross_every`` 1, the default) the counts are rebuilt every
    generation, so on ``backend="jnp"`` none are kept: each candidate
    column's entropy comes from one sort of its n codes
    (``measures.sorted_entropy``), with no scatter-add and no (M, B) tensor.
  * Islands: ``num_islands`` independent sub-populations evolved under one
    vmap, with ring elite migration every ``migrate_every`` generations
    (each island's worst ``migrate_frac * phi`` candidates are replaced by
    its neighbour's best).  Multi-start search at no extra wall-clock depth.

Fixed-shape set operations:
  * "choose k random members of a mask" and "refill a mask to size m" use
    rank-of-random-scores tricks (double argsort) — O(M log M), fixed shape.
  * row-set dedup after crossover sorts the child and replaces duplicate
    slots with fresh uniform indices (collision probability ~ n^2/N; a
    surviving duplicate only double-weights one row in the histogram).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .measures import (
    CodedDataset,
    column_entropy_from_counts,
    full_column_entropy,
    sorted_entropy,
    subset_counts,
    MEASURES,
)
from ..kernels.entropy.ops import population_histogram
from ..kernels.gen_dst.ops import fused_delta_fitness
from ..obs.jaxprof import note_trace

__all__ = ["GenDSTConfig", "DSTResult", "gen_dst", "gen_dst_batch",
           "default_dst_size", "random_dst", "gen_dst_path", "GEN_DST_BACKENDS"]

# full-recompute histogram / fused-generation execution backends
# (DESIGN.md §16.3): "jnp" is the bit-level oracle everywhere.
GEN_DST_BACKENDS = ("jnp", "pallas", "pallas_fused")


def _validate_cfg(cfg: "GenDSTConfig") -> None:
    """Shared solo/batched config validation: a bad config must fail fast
    identically on both paths instead of diverging batched-vs-solo."""
    assert cfg.phi % 2 == 0, "population size must be even (pairwise crossover)"
    assert cfg.num_islands >= 1 and cfg.cross_every >= 1 and cfg.migrate_every >= 1
    if cfg.backend not in GEN_DST_BACKENDS:
        raise ValueError(
            f"unknown Gen-DST backend {cfg.backend!r}; expected one of "
            f"{', '.join(GEN_DST_BACKENDS)}")
    if cfg.measure != "entropy" and cfg.backend != "jnp":
        # the Pallas kernels compute entropy histograms only
        raise ValueError(
            f"Gen-DST backend {cfg.backend!r} needs measure='entropy'; "
            f"measure {cfg.measure!r} runs only on backend='jnp'")


class GenDSTConfig(NamedTuple):
    psi: int = 30          # generations
    phi: int = 100         # population size PER ISLAND (must be even)
    xi: float = 0.025      # mutation probability per candidate
    alpha: float = 0.05    # royalty (elite) fraction
    p_rc: float = 0.9      # P(mutate/cross rows) vs columns
    measure: str = "entropy"
    # --- search-loop extensions (DESIGN.md §5.5, §16) ----------------------
    # execution backend: "jnp" (XLA reference, the bit-level oracle),
    # "pallas" (Pallas histogram on full recomputes only), or "pallas_fused"
    # (the §16 kernel: delta-update + fitness fused into one VMEM-resident
    # launch per generation, Pallas histogram on crossover recomputes)
    backend: str = "jnp"
    incremental: bool = True   # delta-update counts on mutation-only gens
    cross_every: int = 1   # crossover every k-th generation (1 = seed-faithful)
    num_islands: int = 1   # independent sub-populations (vmapped)
    migrate_every: int = 5     # generations between elite migrations
    migrate_frac: float = 0.1  # fraction of phi migrated per event


class DSTResult(NamedTuple):
    row_idx: jax.Array     # (n,) int32
    col_mask: jax.Array    # (M,) bool
    fitness: jax.Array     # scalar, = -|F(d) - F(D)|
    history: jax.Array     # (psi,) best fitness per generation
    f_ref: jax.Array       # F(D)
    path: str = ""         # gen_dst_path of the search; "" if no GA ran


def gen_dst_path(cfg: GenDSTConfig) -> str:
    """How a search scores its candidates (DESIGN.md §16.3), from the
    static config the jit specializes on:

    * ``"sort"`` — ``jnp``, entropy, crossover every generation: each
      candidate column's entropy from one sort of its rows' codes; no counts
      are carried, since every generation rebuilds them anyway;
    * ``"counts"`` — ``jnp``, entropy, ``cross_every > 1``: (M, B) counts
      ride the scan carry for the mutation-only generations' row deltas;
    * ``"pallas"`` / ``"pallas_fused"`` — the Pallas kernels' counts;
    * ``"values"`` — a measure other than entropy, on the raw values.
    """
    if cfg.measure != "entropy":
        return "values"
    if cfg.backend == "jnp":
        return "sort" if cfg.cross_every == 1 else "counts"
    return cfg.backend


def default_dst_size(N: int, M: int) -> tuple[int, int]:
    """Paper default DST size: (sqrt(N), 0.25*M), clamped to the data."""
    n = max(2, min(N, int(round(float(N) ** 0.5))))
    m = max(2, min(M, int(round(0.25 * M))))
    return n, m


# ---------------------------------------------------------------------------
# fixed-shape mask utilities
# ---------------------------------------------------------------------------


def _rank_desc(scores: jax.Array) -> jax.Array:
    """rank[i] = position of scores[i] in descending order (0 = largest)."""
    order = jnp.argsort(-scores)
    return jnp.argsort(order)


def _sample_members(key, mask: jax.Array, k) -> jax.Array:
    """Random sub-mask with min(k, |mask|) True entries drawn from ``mask``.

    ``k`` may be a traced scalar."""
    scores = jax.random.uniform(key, mask.shape) - jnp.where(mask, 0.0, jnp.inf)
    return mask & (_rank_desc(scores) < k)


def _refill_to(key, mask: jax.Array, m, forbidden: Optional[jax.Array] = None) -> jax.Array:
    """Add random positions outside ``mask`` (and ``forbidden``) until |mask| = m."""
    deficit = m - mask.sum()
    blocked = mask if forbidden is None else (mask | forbidden)
    scores = jax.random.uniform(key, mask.shape) - jnp.where(blocked, jnp.inf, 0.0)
    add = (~blocked) & (_rank_desc(scores) < deficit)
    return mask | add


def _dedup_rows(key, rows: jax.Array, N: int) -> jax.Array:
    """Sort a row-index vector and replace duplicate slots with fresh indices."""
    s = jnp.sort(rows)
    dup = jnp.concatenate([jnp.zeros((1,), bool), s[1:] == s[:-1]])
    fresh = jax.random.randint(key, rows.shape, 0, N, dtype=rows.dtype)
    return jnp.where(dup, fresh, s)


# ---------------------------------------------------------------------------
# population init
# ---------------------------------------------------------------------------


def _init_population(key, N: int, M: int, n: int, m: int, phi: int, target: int):
    kr, kc, kd = jax.random.split(key, 3)
    rows = jax.random.randint(kr, (phi, n), 0, N, dtype=jnp.int32)
    rows = jax.vmap(_dedup_rows, in_axes=(0, 0, None))(
        jax.random.split(kd, phi), rows, N
    )
    tgt = jnp.zeros((M,), bool).at[target].set(True)
    def one_colmask(k):
        empty = jnp.zeros((M,), bool)
        return _refill_to(k, tgt, m, forbidden=empty) | tgt
    cols = jax.vmap(one_colmask)(jax.random.split(kc, phi))
    return rows, cols


# ---------------------------------------------------------------------------
# fitness
# ---------------------------------------------------------------------------


def _entropy_fitness(codes, B, f_ref, rows, cols):
    """Vectorized fitness over the population (gather-recompute path)."""
    def one(r, cm):
        h = column_entropy_from_counts(subset_counts(codes, r, B))
        cmf = cm.astype(jnp.float32)
        f_d = jnp.sum(h * cmf) / jnp.maximum(cmf.sum(), 1.0)
        return -jnp.abs(f_d - f_ref)
    return jax.vmap(one)(rows, cols)


def _entropy_fitness_of(h, cols, f_ref):
    """Fitness from per-candidate column entropies: (..., M) + (..., M)."""
    cmf = cols.astype(jnp.float32)
    f_d = jnp.sum(h * cmf, axis=-1) / jnp.maximum(cmf.sum(axis=-1), 1.0)
    return -jnp.abs(f_d - f_ref)


def _counts_fitness(counts, cols, f_ref):
    """Fitness from carried per-candidate counts: (..., M, B) + (..., M)."""
    return _entropy_fitness_of(column_entropy_from_counts(counts), cols, f_ref)


def _population_entropy(codes, rows):
    """(..., n) row sets -> (..., M) column entropies, with no counts: one
    sort of each candidate column's codes, laid out (..., M, n)."""
    sub = jnp.take(codes, rows, axis=0)                # (..., n, M)
    return sorted_entropy(jnp.swapaxes(sub, -1, -2))


def _generic_fitness(values, measure_fn, f_ref, rows, cols):
    def one(r, cm):
        return -jnp.abs(measure_fn(values, r, cm) - f_ref)
    return jax.vmap(one)(rows, cols)


def _population_counts(codes, rows, B, *, backend):
    """(..., phi, n) row indices -> (..., phi, M, B) per-candidate counts."""
    lead = rows.shape[:-1]
    n = rows.shape[-1]
    M = codes.shape[1]
    sub = jnp.take(codes, rows.reshape(-1, n), axis=0)        # (P, n, M)
    hist = population_histogram(sub, B, backend=backend)
    return hist.reshape(*lead, M, B)


def _row_delta(codes, counts, old_rows, new_rows, applied):
    """Delta-update per-candidate counts after a one-row mutation.

    counts: (phi, M, B); old_rows/new_rows: (phi,) row indices; applied:
    (phi,) bool — candidates whose mutation actually fired.  Subtracts the
    evicted row's one-hot contribution and adds the fresh row's.
    """
    oc = jnp.take(codes, old_rows, axis=0)        # (phi, M)
    nc = jnp.take(codes, new_rows, axis=0)
    w = applied.astype(jnp.float32)[:, None]      # (phi, 1)
    phi, M = oc.shape
    ai = jnp.arange(phi)[:, None]
    aj = jnp.arange(M)[None, :]
    counts = counts.at[ai, aj, oc].add(-w)
    counts = counts.at[ai, aj, nc].add(w)
    return counts


# ---------------------------------------------------------------------------
# GA operators
# ---------------------------------------------------------------------------


def _mutate_core(key, rows, cols, *, N, M, n, m, xi, p_rc, target):
    """Mutation + the bookkeeping incremental fitness needs.

    Returns (new_rows, new_cols, applied, old_vals, fresh): ``applied`` marks
    candidates whose ROW mutation fired; ``old_vals``/``fresh`` are the
    evicted/inserted row indices (ignored where not applied).
    """
    phi = rows.shape[0]
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    do_mut = jax.random.uniform(k1, (phi,)) < xi
    mut_rows = jax.random.uniform(k2, (phi,)) < p_rc

    # --- row mutation: replace one random slot with a fresh index -----------
    slot = jax.random.randint(k3, (phi,), 0, n)
    fresh = jax.random.randint(k4, (phi,), 0, N, dtype=rows.dtype)
    # skip if fresh already a member (keeps |r ∩ r'| = n-1 semantics cheaply)
    already = (rows == fresh[:, None]).any(axis=1)
    apply_row = do_mut & mut_rows & (~already)
    old_vals = rows[jnp.arange(phi), slot]
    new_rows = rows.at[jnp.arange(phi), slot].set(
        jnp.where(apply_row, fresh, old_vals)
    )

    # --- column mutation: swap one ON (non-target) for one OFF column -------
    tgt = jnp.zeros((M,), bool).at[target].set(True)
    def col_mut(k, cm):
        ka, kb = jax.random.split(k)
        off = _sample_members(ka, cm & (~tgt), 1)   # one member to drop
        on = _sample_members(kb, ~cm, 1)            # one non-member to add
        ok = (off.sum() == 1) & (on.sum() == 1)
        return jnp.where(ok, (cm & ~off) | on, cm)
    mutated_cols = jax.vmap(col_mut)(jax.random.split(k5, phi), cols)
    apply_col = (do_mut & (~mut_rows))[:, None]
    new_cols = jnp.where(apply_col, mutated_cols, cols)
    return new_rows, new_cols, apply_row, old_vals, fresh


def _mutate(key, rows, cols, *, N, M, n, m, xi, p_rc, target):
    new_rows, new_cols, _, _, _ = _mutate_core(
        key, rows, cols, N=N, M=M, n=n, m=m, xi=xi, p_rc=p_rc, target=target
    )
    return new_rows, new_cols


def _crossover_splits(key, half, n, m):
    """Independent row/column crossover split sizes.

    Draws ``s_r`` (how many rows child_ab takes from parent a) and ``s_c``
    (how many columns) from *separate* keys.  A single shared key here
    correlates the two draws — with identical ranges (``n == m - 1``) the
    row and column split points would be bit-identical every generation —
    so each geometry axis gets its own fold of ``key``."""
    ksr, ksc = jax.random.split(key)
    s_r = jax.random.randint(ksr, (half,), 1, jnp.maximum(n, 2))
    s_c = jax.random.randint(ksc, (half,), 1, jnp.maximum(m - 1, 2))
    return s_r, s_c


def _crossover(key, rows, cols, *, N, M, n, m, p_rc, target):
    """Pairwise split-and-swap crossover over the whole population."""
    phi = rows.shape[0]
    half = phi // 2
    kp, kt, ks, kra, krb, kca, kcb, kfa, kfb, kda, kdb = jax.random.split(key, 11)

    perm = jax.random.permutation(kp, phi)
    ra, rb = rows[perm[:half]], rows[perm[half:]]
    ca, cb = cols[perm[:half]], cols[perm[half:]]

    cross_rows = jax.random.uniform(kt, (half,)) < p_rc

    s_r, s_c = _crossover_splits(ks, half, n, m)

    # --- row crossover: child_ab = s rows of a + (n-s) rows of b ------------
    pa = jax.vmap(lambda k, r: jax.random.permutation(k, r))(
        jax.random.split(kra, half), ra
    )
    pb = jax.vmap(lambda k, r: jax.random.permutation(k, r))(
        jax.random.split(krb, half), rb
    )
    take_a = jnp.arange(n)[None, :] < s_r[:, None]
    child_ab_rows = jnp.where(take_a, pa, pb)   # s from a, rest from b
    child_ba_rows = jnp.where(take_a, pb, pa)
    child_ab_rows = jax.vmap(_dedup_rows, in_axes=(0, 0, None))(
        jax.random.split(kda, half), child_ab_rows, N
    )
    child_ba_rows = jax.vmap(_dedup_rows, in_axes=(0, 0, None))(
        jax.random.split(kdb, half), child_ba_rows, N
    )

    # --- column crossover: union of s members of a and (m-s) of b, refill ---
    tgt = jnp.zeros((M,), bool).at[target].set(True)
    def col_child(k, kf, cma, cmb, s):
        k1, k2 = jax.random.split(k)
        u = _sample_members(k1, cma & ~tgt, s) | _sample_members(
            k2, cmb & ~tgt, m - 1 - s
        )
        u = u | tgt
        return _refill_to(kf, u, m)
    child_ab_cols = jax.vmap(col_child)(
        jax.random.split(kca, half), jax.random.split(kfa, half), ca, cb, s_c
    )
    child_ba_cols = jax.vmap(col_child)(
        jax.random.split(kcb, half), jax.random.split(kfb, half), cb, ca, s_c
    )

    # row-cross keeps own columns; col-cross keeps own rows (paper §3.3)
    ab_rows = jnp.where(cross_rows[:, None], child_ab_rows, ra)
    ba_rows = jnp.where(cross_rows[:, None], child_ba_rows, rb)
    ab_cols = jnp.where(cross_rows[:, None], ca, child_ab_cols)
    ba_cols = jnp.where(cross_rows[:, None], cb, child_ba_cols)

    new_rows = jnp.concatenate([ab_rows, ba_rows], axis=0)
    new_cols = jnp.concatenate([ab_cols, ba_cols], axis=0)
    return new_rows, new_cols


def _select_idx(key, fitness, *, alpha):
    """Royalty tournament: keep top alpha*phi, sample the rest ∝ fitness."""
    phi = fitness.shape[0]
    n_elite = max(1, int(round(alpha * phi)))
    order = jnp.argsort(-fitness)
    elite = order[:n_elite]
    # fitness-proportional sampling on shifted fitness (fitness <= 0)
    w = fitness - fitness.min() + 1e-9
    drawn = jax.random.choice(key, phi, (phi - n_elite,), replace=True, p=w / w.sum())
    return jnp.concatenate([elite, drawn])


def _select(key, rows, cols, fitness, *, alpha):
    keep = _select_idx(key, fitness, alpha=alpha)
    return rows[keep], cols[keep]


# ---------------------------------------------------------------------------
# island migration
# ---------------------------------------------------------------------------


def _ring_migrate(rows, cols, counts, fit, *, k):
    """Replace each island's worst k candidates with its neighbour's best k.

    All arrays carry an (num_islands, phi, ...) leading pair; the ring is a
    roll over the island axis, so migration is one gather + one scatter.
    """
    I, phi = fit.shape

    def gather(x, idx):
        return jnp.take_along_axis(
            x, idx.reshape(idx.shape + (1,) * (x.ndim - 2)), axis=1
        )

    order = jnp.argsort(-fit, axis=1)
    best_i, worst_i = order[:, :k], order[:, phi - k:]
    ai = jnp.arange(I)[:, None]

    def swap(x):
        incoming = jnp.roll(gather(x, best_i), 1, axis=0)
        return x.at[ai, worst_i].set(incoming)

    return swap(rows), swap(cols), swap(counts), swap(fit)


# ---------------------------------------------------------------------------
# main entry point
# ---------------------------------------------------------------------------


def _gen_dst_core(key, codes, values, n, m, cfg: GenDSTConfig, B, target):
    """Trace-level GA body shared by the solo jit and the vmapped batch jit
    (``gen_dst_batch``): one definition, so a batched search runs the exact
    same per-search math as a solo one."""
    note_trace("gen_dst._gen_dst_core")   # body runs only while tracing
    N, M = codes.shape
    I, phi = cfg.num_islands, cfg.phi
    entropy = cfg.measure == "entropy"
    use_fused = cfg.backend == "pallas_fused"
    path = gen_dst_path(cfg)
    carry_counts = path not in ("sort", "values")

    def pop_counts(rows):
        # full-recompute histograms: the fused backend shares the entropy
        # kernel's histogram path (DESIGN.md §16.3)
        hist_backend = "pallas" if cfg.backend in ("pallas", "pallas_fused") \
            else "jnp"
        return _population_counts(codes, rows, B, backend=hist_backend)

    if entropy:
        h_full = full_column_entropy(codes, B)
        f_ref = h_full.mean()
    else:
        measure_fn = MEASURES[cfg.measure]
        f_ref = measure_fn(values)

    def fitness_of(rows, cols, counts):
        if path == "sort":
            return _entropy_fitness_of(_population_entropy(codes, rows),
                                       cols, f_ref)
        if entropy:
            return _counts_fitness(counts, cols, f_ref)
        return jax.vmap(
            lambda r, c: _generic_fitness(values, measure_fn, f_ref, r, c)
        )(rows, cols)

    mutate1 = functools.partial(
        _mutate_core, N=N, M=M, n=n, m=m, xi=cfg.xi, p_rc=cfg.p_rc, target=target
    )
    cross1 = functools.partial(
        _crossover, N=N, M=M, n=n, m=m, p_rc=cfg.p_rc, target=target
    )

    k0, kloop = jax.random.split(key)
    rows, cols = jax.vmap(
        lambda kk: _init_population(kk, N, M, n, m, phi, target)
    )(jax.random.split(k0, I))                                  # (I, phi, ...)
    counts0 = (pop_counts(rows) if carry_counts
               else jnp.zeros((I, phi, 1, 1), jnp.float32))
    fit0 = fitness_of(rows, cols, counts0)
    flat0 = fit0.reshape(-1)
    b0 = jnp.argmax(flat0)
    carry0 = (
        rows, cols, counts0,
        flat0[b0], rows.reshape(I * phi, n)[b0], cols.reshape(I * phi, M)[b0],
        kloop,
    )

    def generation(carry, gen_idx):
        rows, cols, counts, best_f, best_r, best_c, key = carry
        key, km, kx, ksel = jax.random.split(key, 4)

        rows1, cols1, applied, old_vals, fresh = jax.vmap(mutate1)(
            jax.random.split(km, I), rows, cols
        )
        xkeys = jax.random.split(kx, I)

        if use_fused:
            # §16 path: the cond only decides *which counts and delta* feed
            # the fused kernel; delta-update + fitness always run as one
            # launch.  Crossover generations rebuild histograms on the Pallas
            # path and pass a zero delta, so both branches share one
            # fitness code path (and one jaxpr shape for the cond).
            no_delta = jnp.zeros_like(applied)

            def with_cross(_):
                rows2, cols2 = jax.vmap(cross1)(xkeys, rows1, cols1)
                return rows2, cols2, pop_counts(rows2), no_delta

            def without_cross(_):
                if cfg.incremental:
                    return rows1, cols1, counts, applied
                return rows1, cols1, pop_counts(rows1), no_delta

            if cfg.cross_every == 1:
                rows2, cols2, counts_b, app = with_cross(None)
            else:
                rows2, cols2, counts_b, app = jax.lax.cond(
                    gen_idx % cfg.cross_every == 0,
                    with_cross, without_cross, None,
                )
            counts2, fit = fused_delta_fitness(
                counts_b,
                jnp.take(codes, old_vals, axis=0),
                jnp.take(codes, fresh, axis=0),
                app, cols2, f_ref,
                backend="pallas_fused",
            )
        else:
            def with_cross(_):
                rows2, cols2 = jax.vmap(cross1)(xkeys, rows1, cols1)
                counts2 = pop_counts(rows2) if carry_counts else counts
                return rows2, cols2, counts2

            def without_cross(_):
                if not carry_counts:
                    return rows1, cols1, counts
                if cfg.incremental:
                    counts2 = jax.vmap(
                        lambda c, o, f_, a: _row_delta(codes, c, o, f_, a)
                    )(counts, old_vals, fresh, applied)
                else:
                    counts2 = pop_counts(rows1)
                return rows1, cols1, counts2

            if cfg.cross_every == 1:
                rows2, cols2, counts2 = with_cross(None)
            else:
                rows2, cols2, counts2 = jax.lax.cond(
                    gen_idx % cfg.cross_every == 0, with_cross, without_cross,
                    None,
                )
            fit = fitness_of(rows2, cols2, counts2)             # (I, phi)
        flat = fit.reshape(-1)
        g = jnp.argmax(flat)
        better = flat[g] > best_f
        best_f = jnp.where(better, flat[g], best_f)
        best_r = jnp.where(better, rows2.reshape(I * phi, n)[g], best_r)
        best_c = jnp.where(better, cols2.reshape(I * phi, M)[g], best_c)

        if I > 1:
            k_mig = max(1, int(round(cfg.migrate_frac * phi)))
            rows2, cols2, counts2, fit = jax.lax.cond(
                (gen_idx + 1) % cfg.migrate_every == 0,
                lambda op: _ring_migrate(*op, k=k_mig),
                lambda op: op,
                (rows2, cols2, counts2, fit),
            )

        keep = jax.vmap(lambda kk, f_: _select_idx(kk, f_, alpha=cfg.alpha))(
            jax.random.split(ksel, I), fit
        )                                                       # (I, phi)

        def take(x):
            return jnp.take_along_axis(
                x, keep.reshape(keep.shape + (1,) * (x.ndim - 2)), axis=1
            )

        carry_out = (take(rows2), take(cols2), take(counts2),
                     best_f, best_r, best_c, key)
        return carry_out, best_f

    carry, history = jax.lax.scan(generation, carry0, jnp.arange(cfg.psi))
    _, _, _, best_f, best_r, best_c, _ = carry
    return best_r, best_c, best_f, history, f_ref


_gen_dst_jit = functools.partial(
    jax.jit, static_argnames=("n", "m", "cfg", "B", "target")
)(_gen_dst_core)


@functools.partial(jax.jit, static_argnames=("n", "m", "cfg", "B", "target"))
def _gen_dst_batch_jit(keys, codes, values, n, m, cfg: GenDSTConfig, B, target):
    return jax.vmap(
        lambda k, cd, vl: _gen_dst_core(k, cd, vl, n, m, cfg, B, target)
    )(keys, codes, values)


def gen_dst(
    key: jax.Array,
    coded: CodedDataset,
    n: Optional[int] = None,
    m: Optional[int] = None,
    cfg: GenDSTConfig = GenDSTConfig(),
) -> DSTResult:
    """Run Gen-DST on a factorized dataset; returns the best DST found."""
    N, M = coded.codes.shape
    dn, dm = default_dst_size(N, M)
    n = dn if n is None else min(n, N)
    m = dm if m is None else min(m, M)
    _validate_cfg(cfg)
    best_r, best_c, best_f, history, f_ref = _gen_dst_jit(
        key, coded.codes, coded.values, n, m, cfg, coded.max_bins, coded.target_col
    )
    return DSTResult(best_r, best_c, best_f, history, f_ref, gen_dst_path(cfg))


def gen_dst_batch(
    keys,
    codeds,
    n: Optional[int] = None,
    m: Optional[int] = None,
    cfg: GenDSTConfig = GenDSTConfig(),
) -> list[DSTResult]:
    """Run Gen-DST on several same-shaped datasets in one vmapped dispatch.

    ``keys``/``codeds`` are parallel sequences; every dataset must share the
    same ``codes`` shape, ``max_bins`` and ``target_col`` (the static axes
    of the jitted GA).  The searches are independent — vmap only changes the
    dispatch granularity, exactly like the AutoML engine's cross-job rung
    merge — so each result matches a solo ``gen_dst`` run with the same key.
    The service scheduler batches concurrent cache-miss jobs through this
    (DESIGN.md §12.4)."""
    if len(keys) != len(codeds) or not codeds:
        raise ValueError("gen_dst_batch: keys and codeds must be equal-length"
                         " non-empty sequences")
    c0 = codeds[0]
    for c in codeds[1:]:
        if (c.codes.shape != c0.codes.shape or c.max_bins != c0.max_bins
                or c.target_col != c0.target_col):
            raise ValueError("gen_dst_batch: all datasets must share the "
                             "codes shape, max_bins, and target_col")
    N, M = c0.codes.shape
    dn, dm = default_dst_size(N, M)
    n = dn if n is None else min(n, N)
    m = dm if m is None else min(m, M)
    _validate_cfg(cfg)
    rb, cb, fb, hist, f_ref = _gen_dst_batch_jit(
        jnp.stack(list(keys)),
        jnp.stack([c.codes for c in codeds]),
        jnp.stack([c.values for c in codeds]),
        n, m, cfg, c0.max_bins, c0.target_col,
    )
    path = gen_dst_path(cfg)
    return [DSTResult(rb[i], cb[i], fb[i], hist[i], f_ref[i], path)
            for i in range(len(codeds))]


def random_dst(key, coded: CodedDataset, n: Optional[int] = None, m: Optional[int] = None):
    """A uniformly random DST (the paper's trivial baseline building block)."""
    N, M = coded.codes.shape
    dn, dm = default_dst_size(N, M)
    n = dn if n is None else min(n, N)
    m = dm if m is None else min(m, M)
    rows, cols = _init_population(key, N, M, n, m, 2, coded.target_col)
    return DSTResult(rows[0], cols[0], jnp.float32(jnp.nan), jnp.zeros((0,)), jnp.float32(jnp.nan))
