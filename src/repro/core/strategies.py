"""SubsetStrategy registry — "how the subset is found" as a pluggable axis.

The paper frames SubStrat as a *wrapper* strategy around any AutoML tool
(§1.1), and its own evaluation (§4.2, Table 3) treats subset selection as a
family of interchangeable methods: Gen-DST, Monte-Carlo search, bandits,
greedy selection, clustering, information gain.  Related work pushes the
same framing further (ASP's automatic proxy-data selection, arXiv
2310.11478; Layered TPOT's staged subset evaluation, arXiv 1801.06007).
This module makes that the API: every way of producing a
measure-preserving subset is a **SubsetStrategy** — a callable

    (key, coded: CodedDataset, n, m, **opts) -> DSTResult-like

registered under a name — and every strategy's output is normalized to one
uniform host-side ``SubsetResult``, which is what ``plan()``/``execute()``
(core/plan.py) and the service layer consume.  Because the payload is
uniform, *any* registered strategy can be cached by the DST cache and
served by the scheduler, not just Gen-DST.

Strategies that expose a ``batch_fn`` can additionally evaluate several
same-shaped searches in one vmapped dispatch (``gen_dst_batch``): the
scheduler uses this to fuse concurrent cache-miss jobs' searches the way
rung cohorts merge (DESIGN.md §12.4).

Third-party registration::

    from repro.core.strategies import register_strategy
    register_strategy("my_dst", my_fn)           # -> usable in any Plan

Unknown names raise ``KeyError`` listing every registered strategy.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .gen_dst import DSTResult, GenDSTConfig, gen_dst, gen_dst_batch, random_dst
from .measures import (
    CodedDataset,
    column_entropy_from_counts,
    full_column_entropy,
    subset_counts,
)

__all__ = [
    "SubsetResult", "StrategySpec", "register_strategy", "get_strategy",
    "available_strategies", "run_strategy", "run_strategy_batch",
    "asp_proxy_dst", "STRATEGIES",
]


@dataclasses.dataclass(frozen=True)
class SubsetResult:
    """Uniform host-side output of every SubsetStrategy.

    This is the one payload the executor, the DST cache, and the scheduler
    handle — strategies may return richer device-side structures
    (``DSTResult``), but everything downstream of strategy execution sees
    exactly this."""
    row_idx: np.ndarray        # (n,) host int32 row indices
    col_mask: np.ndarray       # (M,) host bool column mask (target incl.)
    fitness: float             # -|F(d) - F(D)| (NaN for unscored strategies)
    strategy: str              # registry name (or "<callable>")
    time_s: float              # wall seconds spent producing the subset
    path: str = ""             # gen_dst_path of a Gen-DST search, else ""


@dataclasses.dataclass(frozen=True)
class StrategySpec:
    """One registered SubsetStrategy.

    ``fn(key, coded, n, m, **opts)`` returns a DSTResult-like with
    ``row_idx`` / ``col_mask`` / ``fitness`` fields.  ``batch_fn``, when
    set, evaluates many same-shaped searches at once:
    ``batch_fn(keys, codeds, n, m, **opts) -> [DSTResult, ...]`` — the
    scheduler merges concurrent cache-miss jobs through it.  ``cacheable``
    marks strategies whose output is a pure function of
    ``(dataset, n, m, opts)`` given the key — those are DST-cache eligible.
    """
    name: str
    fn: Callable
    batch_fn: Optional[Callable] = None
    cacheable: bool = True
    description: str = ""


STRATEGIES: Dict[str, StrategySpec] = {}


def register_strategy(
    name: str,
    fn: Callable,
    *,
    batch_fn: Optional[Callable] = None,
    cacheable: bool = True,
    description: str = "",
    overwrite: bool = False,
) -> StrategySpec:
    """Register a SubsetStrategy under ``name``; returns its spec."""
    if not overwrite and name in STRATEGIES:
        raise ValueError(f"strategy {name!r} already registered "
                         f"(pass overwrite=True to replace)")
    spec = StrategySpec(name=name, fn=fn, batch_fn=batch_fn,
                        cacheable=cacheable, description=description)
    STRATEGIES[name] = spec
    return spec


def available_strategies() -> Tuple[str, ...]:
    return tuple(sorted(STRATEGIES))


def get_strategy(name: str) -> StrategySpec:
    """Look up a registered strategy; unknown names list what exists."""
    try:
        return STRATEGIES[name]
    except KeyError:
        raise ValueError(
            f"unknown subset strategy {name!r}; available strategies: "
            f"{', '.join(available_strategies())}") from None


def _to_subset_result(dst, strategy: str, time_s: float) -> SubsetResult:
    return SubsetResult(
        row_idx=np.asarray(jax.device_get(dst.row_idx)),
        col_mask=np.asarray(jax.device_get(dst.col_mask)),
        fitness=float(dst.fitness),
        strategy=strategy,
        time_s=time_s,
        path=getattr(dst, "path", ""),
    )


def run_strategy(
    strategy: Union[str, Callable],
    key: jax.Array,
    coded: CodedDataset,
    n: Optional[int],
    m: Optional[int],
    opts: Sequence[Tuple[str, object]] = (),
) -> SubsetResult:
    """Execute one strategy and normalize its output to a ``SubsetResult``.

    ``strategy`` is a registry name or a bare callable (the old ``dst_fn``
    escape hatch); ``opts`` is a ``(key, value)`` item sequence (the
    hashable form ``Plan`` carries) forwarded as keyword arguments."""
    if callable(strategy):
        fn, name = strategy, getattr(strategy, "__name__", "<callable>")
        kwargs = dict(opts)
    else:
        spec = get_strategy(strategy)
        fn, name = spec.fn, spec.name
        kwargs = dict(opts)
    t0 = time.perf_counter()
    dst = fn(key, coded, n, m, **kwargs)
    return _to_subset_result(dst, name, time.perf_counter() - t0)


def run_strategy_batch(
    strategy: str,
    keys: Sequence[jax.Array],
    codeds: Sequence[CodedDataset],
    n: Optional[int],
    m: Optional[int],
    opts: Sequence[Tuple[str, object]] = (),
) -> List[SubsetResult]:
    """Execute one batchable strategy over several same-shaped datasets in
    a single vmapped dispatch; falls back to per-dataset execution when the
    strategy has no ``batch_fn``."""
    spec = get_strategy(strategy)
    t0 = time.perf_counter()
    if spec.batch_fn is None:
        return [run_strategy(strategy, k, c, n, m, opts)
                for k, c in zip(keys, codeds)]
    dsts = spec.batch_fn(keys, codeds, n, m, **dict(opts))
    share = (time.perf_counter() - t0) / max(len(dsts), 1)
    return [_to_subset_result(d, spec.name, share) for d in dsts]


# ---------------------------------------------------------------------------
# ASP-style proxy scorer (arXiv 2310.11478 flavor)
# ---------------------------------------------------------------------------


def _entropy_fitness_of(coded: CodedDataset, rows: jax.Array, cm: jax.Array):
    f_ref = full_column_entropy(coded.codes, coded.max_bins).mean()
    h = column_entropy_from_counts(
        subset_counts(coded.codes, rows, coded.max_bins))
    cmf = cm.astype(jnp.float32)
    f_d = jnp.sum(h * cmf) / jnp.maximum(cmf.sum(), 1.0)
    return -jnp.abs(f_d - f_ref), f_ref


def asp_proxy_dst(key, coded: CodedDataset, n=None, m=None, *,
                  hard_frac: float = 0.5):
    """ASP-style automatic proxy-data selection (cf. arXiv 2310.11478).

    Instead of searching for a measure-preserving subset, score each row by
    a cheap *proxy* of its training value and assemble the subset directly:

    - **Columns**: the ``m-1`` highest information-gain features (the proxy
      model's relevance ranking) + the target.
    - **Rows**: per-class stratified selection by a nearest-class-centroid
      margin (distance to own centroid minus distance to the best other
      centroid — the proxy model's difficulty score).  Each class gets a
      slot count proportional to its frequency (>= 1, so rare classes
      survive), filled with an even quantile sweep over that class's
      difficulty ranking: a ``hard_frac``-controlled mix of easy
      (prototypical) and hard (boundary) examples.

    One pass over the data, no search loop; the returned fitness is the
    same entropy score every other strategy reports, so ASP subsets are
    comparable to searched ones."""
    from .baselines import _ig_cols, _resolve_nm  # no import cycle

    n, m = _resolve_nm(coded, n, m)
    tgt = coded.target_col

    # columns: IG ranking (proxy feature relevance) — the shared rule the
    # IG baselines use (top m-1 by gain + the target)
    col_mask = np.asarray(jax.device_get(_ig_cols(coded, m)))

    # rows: class-stratified margin quantiles (proxy difficulty)
    vals = np.asarray(jax.device_get(coded.values))
    y = np.asarray(jax.device_get(coded.codes))[:, tgt]
    feats = np.delete(np.arange(vals.shape[1]), tgt)
    Z = vals[:, feats]
    Z = (Z - Z.mean(0)) / (Z.std(0) + 1e-9)
    classes, counts = np.unique(y, return_counts=True)
    cents = np.stack([Z[y == c].mean(0) for c in classes])       # (C, d)
    d2 = ((Z[:, None, :] - cents[None]) ** 2).sum(-1)            # (N, C)
    own = d2[np.arange(len(y)), np.searchsorted(classes, y)]
    other = np.where(
        np.arange(len(classes))[None] == np.searchsorted(classes, y)[:, None],
        np.inf, d2).min(1)
    margin = own - other          # low = prototypical, high = boundary

    # proportional slots, every class >= 1; trim largest classes on overflow
    slots = np.maximum(1, np.round(n * counts / counts.sum()).astype(int))
    while slots.sum() > n:
        slots[np.argmax(slots)] -= 1
    while slots.sum() < n:
        slots[np.argmax(counts - slots)] += 1

    seed = int(np.asarray(jax.device_get(jax.random.randint(
        jax.random.fold_in(key, 0xA59), (), 0, np.iinfo(np.int32).max))))
    rng = np.random.default_rng(seed)
    rows = []
    for cls, k in zip(classes, slots):
        members = np.flatnonzero(y == cls)
        k = min(int(k), len(members))
        order = members[np.argsort(margin[members])]
        # quantile sweep over the easy..hard ranking; hard_frac biases how
        # deep into the boundary region the sweep reaches
        span = max(1, int(round(len(order) * (0.5 + 0.5 * hard_frac))))
        pick = np.unique(np.linspace(0, span - 1, k).round().astype(int))
        chosen = order[pick]
        if len(chosen) < k:   # rounding collisions: fill with random members
            pool = np.setdiff1d(order, chosen)
            chosen = np.concatenate(
                [chosen, rng.choice(pool, k - len(chosen), replace=False)])
        rows.append(chosen)
    row_idx = np.sort(np.concatenate(rows))[:n].astype(np.int32)

    rows_j = jnp.asarray(row_idx)
    cm_j = jnp.asarray(col_mask)
    fitness, f_ref = _entropy_fitness_of(coded, rows_j, cm_j)
    return DSTResult(rows_j, cm_j, fitness, jnp.zeros((0,)), f_ref)


# ---------------------------------------------------------------------------
# built-in registrations
# ---------------------------------------------------------------------------


def _register_builtins() -> None:
    from . import baselines as B

    def _gen(key, coded, n, m, *, cfg: GenDSTConfig = GenDSTConfig(), **kw):
        if kw:
            cfg = cfg._replace(**kw)
        return gen_dst(key, coded, n, m, cfg)

    def _gen_batch(keys, codeds, n, m, *, cfg: GenDSTConfig = GenDSTConfig(),
                   **kw):
        if kw:
            cfg = cfg._replace(**kw)
        return gen_dst_batch(keys, codeds, n, m, cfg)

    def _gen_islands(key, coded, n, m, *, cfg: GenDSTConfig = GenDSTConfig(),
                     num_islands: int = 4, **kw):
        cfg = cfg._replace(num_islands=num_islands, **kw)
        return gen_dst(key, coded, n, m, cfg)

    def _gen_islands_batch(keys, codeds, n, m, *,
                           cfg: GenDSTConfig = GenDSTConfig(),
                           num_islands: int = 4, **kw):
        cfg = cfg._replace(num_islands=num_islands, **kw)
        return gen_dst_batch(keys, codeds, n, m, cfg)

    register_strategy("gen_dst", _gen, batch_fn=_gen_batch,
                      description="the paper's genetic DST search (§3.3)")
    register_strategy("gen_dst_islands", _gen_islands,
                      batch_fn=_gen_islands_batch,
                      description="island-parallel Gen-DST (DESIGN.md §5.5)")
    register_strategy("random", random_dst, cacheable=False,
                      description="uniform random subset (trivial baseline)")
    register_strategy("mc", B.mc_dst,
                      description="Monte-Carlo search (paper §4.2 cat. A)")
    register_strategy("mab", B.mab_dst,
                      description="eps-greedy multi-arm bandit (cat. B)")
    register_strategy("greedy_seq", B.greedy_seq_dst,
                      description="greedy rows-then-columns (cat. C)")
    register_strategy("greedy_mult", B.greedy_mult_dst,
                      description="greedy row+column co-selection (cat. C)")
    register_strategy("km", B.km_dst,
                      description="k-means representatives (cat. D)")
    register_strategy("ig_rand", B.ig_rand_dst,
                      description="IG columns + random rows (cat. E)")
    register_strategy("ig_km", B.ig_km_dst,
                      description="IG columns + k-means rows (cat. E)")
    register_strategy("asp_proxy", asp_proxy_dst,
                      description="ASP-style proxy-data scorer "
                                  "(arXiv 2310.11478 flavor)")


_register_builtins()
