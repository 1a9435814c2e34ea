"""Batched population trainer for the AutoML engine (DESIGN.md §10.3).

The sequential reference path (``engine._eval_rung_loop``) trains one trial
at a time: every distinct ``(family, hp)`` combination compiles its own XLA
program and pays a host round-trip per trial, and closed-form families are
fit eagerly op-by-op.  This module instead advances a whole
successive-halving rung cohort at once:

- **Pipelines as gather/scale ops.**  Each distinct ``(preproc, frac)``
  pair becomes one full-width data *variant*: the preprocessor's per-column
  affine map applied to all ``d`` columns, with non-selected columns zeroed
  in place (zero columns are inert for every family, so this matches the
  loop backend's column slicing).  Variants are stacked once into a cached
  ``(V, N, d)`` tensor; each trial carries a variant id and the jitted
  kernels gather its rows on device — no per-trial Python slicing.
- **Struct-of-arrays params.**  Trials are grouped by
  ``(family,) + shape_hps`` (HPs that change param shapes, e.g. MLP
  depth/width).  Width handling is regime-aware: small, dispatch-bound
  cohorts (``N <= WIDTH_PAD_MAX_ROWS``) pad MLP widths to the sub-batch max
  so all same-depth trials share one scan, while large, flop-bound cohorts
  split per width (padding there would inflate compute up to 16x).  Within
  a sub-batch, params stack leaf-wise into one pytree with a leading cohort
  axis: zero-init families build their init inside the jitted program; MLP
  inits at the loop backend's exact shapes with the loop backend's
  per-(trial, rung) keys, feature rows scattered into the full-width layout.
- **One dispatch per rung.**  Gradient families run one ``jax.vmap``-ed
  Adam ``lax.scan`` per sub-batch (the trajectory is ``models.adam_train``,
  shared with the loop backend; per-trial ``lr``/``l2`` as traced scalars);
  closed-form families one vmapped fit; accuracy evals are fused in.  With
  no wall-clock budget the whole rung is a single jitted program
  (``_eval_rung_fused``) and one host sync; with a budget active each
  sub-batch dispatches separately so the cutoff can land between them.

Promotion stays in ``engine.sh_promote`` (an on-device top-k mask) shared
with the loop backend; winner params are unpadded back to the sequential
shapes so downstream consumers are backend-agnostic (parity: §10.4).

**Cross-job cohort merge** (DESIGN.md §11.4): every trial is tagged with a
job slot and gathers its own job's data variant (``vids``), label vector
(``yids`` into a stacked ``(J, N)`` label tensor), and — for MLP — its own
job's ``(seed, trial_id, rung)`` init key.  ``eval_rung_cohorts`` exploits
this to fuse rung cohorts from *different* jobs with compatible data shapes
into one dispatch: sub-batches group by ``(family,) + shape_hps`` across
jobs, so eight 6-trial jobs cost one program launch instead of eight.
Merging changes dispatch granularity only — vmapped trials are independent,
so per-trial math is identical to single-job execution.

**Continuous rung batching** (DESIGN.md §13): ``eval_trial_megabatch``
drops the last merge precondition — cohorts no longer need to sit at the
same ``(rung_i, epochs)``.  Each trial additionally carries its rung cursor
(MLP init keys fold in the trial's *own* rung) and its remaining epoch
budget as a per-trial **step mask**: the shared Adam scan runs
``max(steps)`` slots and a trial with ``n_steps`` remaining freezes its
``(params, m, v)`` carry after ``n_steps`` of them
(``models.adam_train(n_steps=...)``) — the same inert-padding trick as the
row/class masks, applied to the time axis.  A 2-epoch trial and an 8-epoch
neighbor therefore share one jitted dispatch, which is what lets the
scheduler keep a single standing megabatch that trials join and leave as
they are promoted or culled, instead of lockstep ``(rung_i, epochs)``
buckets.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace
from ..obs.jaxprof import note_trace
from .engine import (
    TrialCohort, _apply_preproc, _fit_preproc, _select_features, _trial_key,
)
from .models import (
    CLASS_MASK_NEG, FAMILIES, adam_train, masked_accuracy, masked_fit,
    masked_loss,
)

__all__ = ["eval_rung_batched", "eval_rung_cohorts", "eval_trial_megabatch"]


# ---------------------------------------------------------------------------
# pipeline variants: (preproc, feature_frac) -> full-width transformed data
# ---------------------------------------------------------------------------


def _variant(ctx, preproc: str, frac: float) -> int:
    """Ensure the (preproc, frac) variant exists; return its stable index.

    A variant keeps all ``d`` columns — non-selected ones zeroed — so every
    trial shares one array shape and the cohort kernels can gather by index.
    """
    cache = ctx["variant_cache"]
    vkey = (preproc, frac)
    if vkey not in cache:
        X_tr, X_val = ctx["X_tr"], ctx["X_val"]
        stats = _fit_preproc(preproc, X_tr)
        fidx = _select_features(frac, ctx)
        mask = np.zeros((X_tr.shape[1],), np.float32)
        mask[fidx] = 1.0
        cache[vkey] = {
            "id": len(cache),
            "stats": stats,
            "fidx": fidx,
            "Xtr": _apply_preproc(preproc, stats, X_tr) * mask,
            "Xval": _apply_preproc(preproc, stats, X_val) * mask,
        }
        ctx.pop("variant_stack", None)   # invalidate the stacked tensor
    return cache[vkey]["id"]


def _variant_stack(ctx):
    """(V, N, d) / (V, Nval, d) stacked variants, rebuilt only on growth."""
    if "variant_stack" not in ctx:
        vs = sorted(ctx["variant_cache"].values(), key=lambda v: v["id"])
        ctx["variant_stack"] = (
            jnp.asarray(np.stack([v["Xtr"] for v in vs]), jnp.float32),
            jnp.asarray(np.stack([v["Xval"] for v in vs]), jnp.float32),
        )
    return ctx["variant_stack"]


def _variants(ctx, specs) -> Tuple[List[int], tuple]:
    """Each spec's variant id in ``ctx`` and the ``(V, N, d)`` stacks.

    A call that builds anything (a new variant, hence a new stack) holds
    the ``automl.variants`` profiler annotation and appends ``(wall t0,
    wall t1, seconds)`` to ``ctx["variant_builds"]``, which the scheduler
    records as the job's ``variants`` span.  A search registers its whole
    population at its first rung, so later rungs build nothing."""
    cache = ctx["variant_cache"]
    if "variant_stack" in ctx and all(
            (s.preproc, s.feature_frac) in cache for s in specs):
        return ([cache[(s.preproc, s.feature_frac)]["id"] for s in specs],
                ctx["variant_stack"])
    t0, w0 = time.perf_counter(), time.time()
    with trace.annotate("automl.variants"):
        vids = [_variant(ctx, s.preproc, s.feature_frac) for s in specs]
        stacks = _variant_stack(ctx)
    ctx.setdefault("variant_builds", []).append(
        (w0, time.time(), time.perf_counter() - t0))
    return vids, stacks


def _concat_padded(parts, N_to: int, d_to: int):
    """Trace-level merge of per-job variant stacks into one (ΣV, N, d)
    tensor, zero-padding each part to the group-maximal shape.  Runs inside
    the jitted rung program so the padding fuses with the downstream
    gathers instead of materializing eagerly per rung."""
    if len(parts) == 1 and parts[0].shape[1] == N_to and parts[0].shape[2] == d_to:
        return parts[0]
    return jnp.concatenate([
        jnp.pad(x, ((0, 0), (0, N_to - x.shape[1]), (0, d_to - x.shape[2])))
        for x in parts])


# ---------------------------------------------------------------------------
# param padding / unpadding between loop-backend and full-width layouts
# ---------------------------------------------------------------------------


# Below this many training rows the cohort is dispatch-bound, so MLP widths
# pad to the sub-batch max (zero padding is gradient-inert — DESIGN.md §10.4)
# and all depths-equal trials share one scan.  Above it the cohort is
# flop-bound and width padding would inflate compute up to 16x, so widths
# split into separate sub-batches instead (DESIGN.md §10.3).
WIDTH_PAD_MAX_ROWS = 2048


def _unpad_linear(params, fidx, hp, c) -> dict:
    return {"w": params["w"][np.asarray(fidx)][:, :c], "b": params["b"][:c]}


def _unpad_mlp(params, fidx, hp, c) -> dict:
    width = int(hp["width"])
    layers, L = params["layers"], len(params["layers"])
    out = []
    for i, lyr in enumerate(layers):
        w, b = lyr["w"], lyr["b"]
        w = w[np.asarray(fidx)] if i == 0 else w[:width]
        if i < L - 1:            # hidden outputs may be width-padded
            w, b = w[:, :width], b[:width]
        else:                    # output classes may be class-padded (§12.3)
            w, b = w[:, :c], b[:c]
        out.append({"w": w, "b": b})
    return {"layers": out}


def _unpad_gnb(params, fidx, hp, c) -> dict:
    cols = np.asarray(fidx)
    return {"mean": params["mean"][:c, cols], "var": params["var"][:c, cols],
            "prior": params["prior"][:c]}


def _unpad_centroid(params, fidx, hp, c) -> dict:
    return {"cent": params["cent"][:c, np.asarray(fidx)]}


_UNPAD: Dict[str, Callable] = {
    "logreg": _unpad_linear, "linear_svm": _unpad_linear, "mlp": _unpad_mlp,
    "gnb": _unpad_gnb, "centroid": _unpad_centroid,
}


def _unpad_trial(family: str, params_b, j: int, fidx, hp, c: int):
    single = jax.tree.map(lambda x: x[j], params_b)
    return _UNPAD[family](single, fidx, hp, c)


# ---------------------------------------------------------------------------
# jitted cohort kernels: vmapped train+eval / fit+eval per family sub-batch
# ---------------------------------------------------------------------------


def _val_acc(fam, params, X, y):
    return (jnp.argmax(fam.predict(params, X), axis=1) == y).mean()


def _train_eval_cohort(fam, params0, Xall, Xall_val, Yall, Yall_val,
                       vids, yids, hp, c, epochs, masks=None, steps=None):
    """Trace-level core: vmapped Adam ``lax.scan`` fused with the
    validation-accuracy eval.  The trajectory is ``models.adam_train`` — the
    same definition the sequential backend runs — with the learning rate and
    regularisation arriving as traced per-trial scalars; each trial gathers
    its data variant from ``Xall`` and its job's labels from the stacked
    ``(J, N)`` label tensor ``Yall`` on device (single-job runs pass J=1).

    ``masks`` is None on exact-shape dispatches; a heterogeneous-shape merge
    passes ``(Wtr (J, N), Wval (J, Nval), Cmask (J, c))`` row/class padding
    masks and the trial trains through the masked loss (DESIGN.md §12.3).

    ``steps`` is None on uniform-rung dispatches; a cross-rung megabatch
    passes per-trial step budgets and each trial's scan carry freezes after
    its own ``steps[i]`` of the ``epochs`` scan slots (DESIGN.md §13.1)."""

    def one(p0, vid, yid, hp1, n_steps):
        X, y = Xall[vid], Yall[yid]
        if masks is None:
            grad_fn = jax.grad(lambda p: fam.loss(p, X, y, c, hp1))
        else:
            w, cm = masks[0][yid], masks[2][yid]
            grad_fn = jax.grad(
                lambda p: masked_loss(fam.name, p, X, y, w, cm, c, hp1))
        params = adam_train(grad_fn, p0, hp1["lr"], epochs, n_steps=n_steps)
        if masks is None:
            return params, _val_acc(fam, params, Xall_val[vid], Yall_val[yid])
        return params, masked_accuracy(
            fam.name, params, Xall_val[vid], Yall_val[yid],
            masks[1][yid], masks[2][yid])

    if steps is None:
        # keep the unmasked scan trace: one() closes over n_steps=None
        return jax.vmap(lambda p0, vid, yid, hp1: one(p0, vid, yid, hp1, None)
                        )(params0, vids, yids, hp)
    return jax.vmap(one)(params0, vids, yids, hp, steps)


def _keyless_cohort(family, T, Xall, Xall_val, Yall, Yall_val, vids, yids,
                    hp, c, epochs, masks=None, steps=None):
    """Zero-init families: the init happens inside the traced program."""
    fam = FAMILIES[family]
    p0 = fam.init(None, Xall.shape[2], c, {})
    params0 = jax.tree.map(lambda x: jnp.broadcast_to(x, (T,) + x.shape), p0)
    return _train_eval_cohort(fam, params0, Xall, Xall_val, Yall, Yall_val,
                              vids, yids, hp, c, epochs, masks, steps)


def _mlp_cohort(seeds, tids, rungs, fidxs, shapes, depth, wmax, d,
                Xall, Xall_val, Yall, Yall_val, vids, yids, hp, c, epochs,
                masks=None, steps=None):
    """MLP sub-batch: loop-identical per-trial init (same
    ``(seed, trial_id, rung)`` key, actual ``(k, width, c_job)`` shapes)
    scattered to the full-feature / ``wmax``-wide / ``c``-class layout,
    stacked, trained, and evaluated.  ``shapes[i] = (k, width, c_i)`` per
    trial; ``seeds`` is per-trial so merged cohorts derive each trial's key
    from its own job's seed, ``rungs`` is per-trial so a cross-rung
    megabatch folds each trial's *own* rung cursor into its key (§13), and
    ``c_i`` is the trial's own class count so a heterogeneous merge
    initializes exactly the solo shapes before class-padding.

    Padded rows/columns are zero and stay zero under Adam (zero input
    columns, ``relu'(0) = 0``; padded class logits are masked out of the
    softmax), so the active block trains exactly like the sequential path
    (DESIGN.md §10.4, §12.3)."""
    fam = FAMILIES["mlp"]
    plist = []
    for i, (k, width, ci) in enumerate(shapes):
        key = _trial_key(seeds[i], tids[i], rungs[i])  # loop-identical derivation
        p0 = fam.init(key, k, ci, {"width": width, "depth": depth})
        layers, L = p0["layers"], len(p0["layers"])
        out = []
        for li, lyr in enumerate(layers):
            w, b = lyr["w"], lyr["b"]
            if k == d and width == wmax and ci == c:
                out.append({"w": w, "b": b})
                continue
            in_dim = d if li == 0 else wmax
            out_dim = c if li == L - 1 else wmax
            buf = jnp.zeros((in_dim, out_dim), w.dtype)
            if li == 0:
                buf = buf.at[fidxs[i][:, None], jnp.arange(w.shape[1])[None, :]].set(w)
            else:
                buf = buf.at[: w.shape[0], : w.shape[1]].set(w)
            bbuf = jnp.zeros((out_dim,), b.dtype).at[: b.shape[0]].set(b)
            out.append({"w": buf, "b": bbuf})
        plist.append({"layers": out})
    params0 = jax.tree.map(lambda *xs: jnp.stack(xs), *plist)
    return _train_eval_cohort(fam, params0, Xall, Xall_val, Yall, Yall_val,
                              vids, yids, hp, c, epochs, masks, steps)


def _closed_cohort(family, Xall, Xall_val, Yall, Yall_val, vids, yids, hp, c,
                   masks=None):
    fam = FAMILIES[family]

    def one(vid, yid, hp1):
        X, y = Xall[vid], Yall[yid]
        if masks is None:
            params = fam.fit_closed(None, X, y, c, hp1)
            return params, _val_acc(fam, params, Xall_val[vid], Yall_val[yid])
        w, cm = masks[0][yid], masks[2][yid]
        params = masked_fit(family, X, y, w, cm, c, hp1)
        return params, masked_accuracy(
            family, params, Xall_val[vid], Yall_val[yid],
            masks[1][yid], cm)

    return jax.vmap(one)(vids, yids, hp)


class _GroupDesc(NamedTuple):
    """Hashable static descriptor of one family sub-batch (jit cache key)."""
    kind: str            # "closed" | "keyless" | "mlp"
    family: str
    T: int
    depth: int = 0
    wmax: int = 0
    shapes: tuple = ()   # mlp: ((k, width, c_trial), ...) per trial


def _run_group(desc, gin, Xall, Xall_val, Yall, Yall_val, c, d,
               epochs, masks=None):
    """Trace-level dispatch of one sub-batch; shared by the fused-rung and
    per-group (budget) paths, so both run identical math.

    Per-trial rung cursors (MLP key derivation) and step budgets ride in
    ``gin``: ``gin["rungs"]`` always for MLP sub-batches, ``gin["steps"]``
    only when the sub-batch mixes step budgets (uniform dispatches keep the
    unmasked scan trace — §13.1)."""
    steps = gin.get("steps")
    if desc.kind == "closed":
        return _closed_cohort(desc.family, Xall, Xall_val, Yall, Yall_val,
                              gin["vids"], gin["yids"], gin["hp"], c, masks)
    if desc.kind == "keyless":
        return _keyless_cohort(desc.family, desc.T, Xall, Xall_val, Yall,
                               Yall_val, gin["vids"], gin["yids"], gin["hp"],
                               c, epochs, masks, steps)
    return _mlp_cohort(gin["seeds"], gin["tids"], gin["rungs"], gin["fidxs"],
                       desc.shapes, desc.depth, desc.wmax, d, Xall, Xall_val,
                       Yall, Yall_val, gin["vids"], gin["yids"], gin["hp"],
                       c, epochs, masks, steps)


@functools.partial(jax.jit, static_argnames=("descs", "c", "d", "epochs"))
def _eval_rung_fused(ginputs, Xparts, Xval_parts, Yall, Yall_val,
                     masks, *, descs, c: int, d: int, epochs: int):
    """One dispatch for the whole rung: every family sub-batch trains and
    evaluates inside a single jitted program (used when no wall-clock budget
    needs mid-rung cutoffs).  With merged cohorts the sub-batches span jobs,
    so this is also one dispatch for the whole *job group*.

    ``Xparts``/``Xval_parts`` are tuples of per-job variant stacks, merged
    (and, when job shapes differ, zero-padded to the ``Yall`` row count /
    static ``d``) at trace level; ``masks`` is None for exact-shape
    dispatches, or the (Wtr, Wval, Cmask) padding tensors of a
    heterogeneous-shape merge (DESIGN.md §12.3).  ``epochs`` is the scan
    length — the max step budget across the dispatch; trials with fewer
    steps carry their budget in ``gin["steps"]`` (DESIGN.md §13.1)."""
    note_trace("batched._eval_rung_fused")   # body runs only while tracing
    Xall = _concat_padded(Xparts, Yall.shape[1], d)
    Xall_val = _concat_padded(Xval_parts, Yall_val.shape[1], d)
    return tuple(
        _run_group(desc, gin, Xall, Xall_val, Yall, Yall_val, c, d,
                   epochs, masks)
        for desc, gin in zip(descs, ginputs))


@functools.partial(jax.jit, static_argnames=("desc", "c", "d", "epochs"))
def _eval_group(gin, Xall, Xall_val, Yall, Yall_val,
                *, desc, c: int, d: int, epochs: int):
    """Single sub-batch dispatch — the budget path, so the engine can check
    the wall clock between sub-batches."""
    note_trace("batched._eval_group")
    return _run_group(desc, gin, Xall, Xall_val, Yall, Yall_val, c, d,
                      epochs)


# ---------------------------------------------------------------------------
# rung drivers: single-job and cross-job merged
# ---------------------------------------------------------------------------


class _TaggedTrial(NamedTuple):
    """One trial of a (possibly merged) rung dispatch."""
    job: int         # job slot = yid into the stacked (J, N) label tensor
    pos: int         # position in its job's cohort
    spec: object     # PipelineSpec
    tid: int         # trial id (PRNG key derivation)
    seed: int        # its job's AutoMLConfig.seed
    vid: int         # index into the merged variant stack
    c: int           # its job's class count (class-padding axis, §12.3)
    rung: int        # its own rung cursor (MLP key derivation, §13)
    steps: int       # its own epoch budget at that rung (step mask, §13.1)


def _group_subbatches(trials: List[_TaggedTrial], pad_widths: bool, variants,
                      epochs_max: int):
    """Group tagged trials by ``(family,) + shape_hps`` into dispatch jobs.

    Returns ``[(trial_indices, desc, gin)]`` — one static descriptor plus
    numpy inputs per sub-batch; numpy args are converted during the jit call,
    no eager dispatches.  Trials from different jobs land in the same
    sub-batch whenever family and shape HPs match — that is the cross-job
    merge.

    ``epochs_max`` is the dispatch-wide scan length.  Gradient sub-batches
    whose trials all train exactly ``epochs_max`` steps omit the ``steps``
    array so uniform (lockstep) dispatches keep the unmasked scan trace;
    mixed-budget sub-batches carry per-trial step masks (§13.1)."""
    groups: Dict[tuple, List[int]] = {}
    for t_i, t in enumerate(trials):
        hp = dict(t.spec.hp)
        fam = FAMILIES[t.spec.family]
        skip = ("width",) if pad_widths and t.spec.family == "mlp" else ()
        gkey = (t.spec.family,) + tuple(hp[k] for k in fam.shape_hps if k not in skip)
        groups.setdefault(gkey, []).append(t_i)

    subbatches: List[tuple] = []   # (trial_indices, desc, gin)
    for gkey, idxs in groups.items():
        family = gkey[0]
        fam = FAMILIES[family]
        gin = {
            "vids": np.asarray([trials[i].vid for i in idxs], np.int32),
            "yids": np.asarray([trials[i].job for i in idxs], np.int32),
            "hp": {k: np.asarray([dict(trials[i].spec.hp)[k] for i in idxs],
                                 np.float32)
                   for k in fam.hp_grid if k not in fam.shape_hps},
        }
        if fam.fit_closed is not None:
            # closed-form fits are epochs-independent: no step mask needed
            desc = _GroupDesc("closed", family, len(idxs))
            subbatches.append((idxs, desc, gin))
            continue
        if any(trials[i].steps != epochs_max for i in idxs):
            gin["steps"] = np.asarray([trials[i].steps for i in idxs],
                                      np.int32)
        if fam.init_keyless:
            desc = _GroupDesc("keyless", family, len(idxs))
        else:   # mlp
            hps = [dict(trials[i].spec.hp) for i in idxs]
            fidxs = tuple(np.asarray(variants[trials[i].vid]["fidx"])
                          for i in idxs)
            shapes = tuple((len(f), int(h["width"]), trials[i].c)
                           for f, h, i in zip(fidxs, hps, idxs))
            gin["tids"] = np.asarray([trials[i].tid for i in idxs], np.int32)
            gin["seeds"] = np.asarray([trials[i].seed for i in idxs], np.int32)
            gin["rungs"] = np.asarray([trials[i].rung for i in idxs], np.int32)
            gin["fidxs"] = fidxs
            desc = _GroupDesc("mlp", family, len(idxs),
                              depth=int(hps[0]["depth"]),
                              wmax=max(w for (_k, w, _c) in shapes),
                              shapes=shapes)
        subbatches.append((idxs, desc, gin))
    return subbatches


def _unpack_results(evaluated, trials, variants, collect_params):
    """One host sync for the whole dispatch; per-trial result tuples.

    Returns ``{trial_index: (val_acc, params, fidx, stats)}``."""
    all_vaccs = np.asarray(jnp.concatenate([v for (_i, v, _f, _pb) in evaluated]))
    results: Dict[int, tuple] = {}
    i = 0
    for idxs, _vaccs, family, params_b in evaluated:
        for j, t_i in enumerate(idxs):
            var = variants[trials[t_i].vid]
            if collect_params:
                # lazy: only the winner's params ever get sliced + unpadded
                # (the engine materializes callables on access)
                params = functools.partial(
                    _unpad_trial, family, params_b, j, var["fidx"],
                    dict(trials[t_i].spec.hp), trials[t_i].c)
            else:
                params = None
            results[t_i] = (float(all_vaccs[i]), params, var["fidx"], var["stats"])
            i += 1
    return results


def eval_rung_batched(cohort, tids, rung_i: int, epochs: int, ctx,
                      out_of_budget, collect_params: bool = True) -> Tuple[list, list]:
    """Evaluate one successive-halving rung as per-family sub-batches.

    Returns ``(scored, positions)`` where ``scored[i]`` is the loop-backend
    tuple ``(spec, val_acc, params, feat_idx, pre_stats)`` and
    ``positions[i]`` is its index into ``cohort``.  ``collect_params=False``
    (non-final rungs) skips the per-trial unpadding — promotion only needs
    accuracies.  Accuracies stay on device until one rung-level sync; when a
    wall-clock budget is active, each sub-batch blocks before the budget
    check so the cutoff sees real execution time."""
    d, c = ctx["X_tr"].shape[1], ctx["n_classes"]
    # dispatch-bound small cohorts pad MLP widths into one sub-batch;
    # flop-bound large ones split per width (see WIDTH_PAD_MAX_ROWS)
    pad_widths = ctx["X_tr"].shape[0] <= WIDTH_PAD_MAX_ROWS

    vids, (Xall_tr, Xall_val) = _variants(ctx, cohort)
    trials = [
        _TaggedTrial(0, pos, spec, int(tids[pos]), int(ctx["seed"]),
                     vids[pos], c, rung_i, epochs)
        for pos, spec in enumerate(cohort)
    ]
    variants = {v["id"]: v for v in ctx["variant_cache"].values()}
    subbatches = _group_subbatches(trials, pad_widths, variants, epochs)
    budget_active = ctx.get("budget_active", False)

    common = (Xall_tr, Xall_val, ctx["y_tr_j"][None], ctx["y_val_j"][None])
    evaluated: List[tuple] = []   # (trial_indices, device vaccs, family, params_b)
    if budget_active:
        # one dispatch per sub-batch, blocking, so the wall-clock cutoff can
        # land between sub-batches
        for idxs, desc, gin in subbatches:
            if out_of_budget() and evaluated:
                break
            params_b, vaccs = _eval_group(gin, *common,
                                          desc=desc, c=c, d=d, epochs=epochs)
            jax.block_until_ready(vaccs)
            evaluated.append((idxs, vaccs, desc.family, params_b))
    else:
        # the whole rung is one jitted program
        outs = _eval_rung_fused(tuple(gin for (_i, _d, gin) in subbatches),
                                (Xall_tr,), (Xall_val,),
                                ctx["y_tr_j"][None], ctx["y_val_j"][None], None,
                                descs=tuple(d_ for (_i, d_, _g) in subbatches),
                                c=c, d=d, epochs=epochs)
        evaluated = [(idxs, vaccs, desc.family, params_b)
                     for (idxs, desc, _g), (params_b, vaccs)
                     in zip(subbatches, outs)]

    results = _unpack_results(evaluated, trials, variants, collect_params)
    # single-job: trial index == cohort position
    eval_pos = sorted(results)
    scored = [(cohort[p],) + results[p] for p in eval_pos]
    return scored, eval_pos


def eval_rung_cohorts(cohorts: List[TrialCohort],
                      collect_params=None) -> List[Tuple[list, list]]:
    """Cross-job rung merge: one fused dispatch for many jobs' cohorts.

    ``cohorts`` is a list of ``TrialCohort``s (``engine.
    search_trial_cohort``) sitting at the same ``(rung_i, epochs)``.  Every
    trial is tagged with its job slot, gathers its own job's data variant
    and label vector on device, and MLP trials derive init keys from their
    own job's ``(seed, trial_id, rung)``.  Returns per-job
    ``(scored, positions)`` pairs in input order.

    Two merge regimes (DESIGN.md §12.3):

    - **Exact** — all cohorts share ``(N_tr, N_val, d, n_classes)``: merging
      changes dispatch granularity only; per-trial math is bit-identical to
      single-job execution (the §11.4 parity argument).
    - **Padded** — shapes differ: every job's data variants are zero-padded
      to the group-maximal ``(N_max, d_max)``, labels to ``(J, N_max)``, and
      trials train through the row/class-masked losses
      (``models.masked_loss``), in which padded rows carry zero weight and
      padded class logits are additively masked out of softmax/hinge/argmax.
      Padding is inert up to floating-point reduction order, so results
      match solo execution to ~1e-6 rather than bit-exactly.

    ``collect_params=None`` collects params iff any cohort asks for them.
    No mid-rung time-budget support: the scheduler only merges jobs without
    ``time_budget_s`` (budgeted jobs run solo via ``eval_rung_batched``).
    """
    rung_i, epochs = cohorts[0].rung_i, cohorts[0].epochs
    for tc in cohorts[1:]:
        if tc.rung_i != rung_i or tc.epochs != epochs:
            raise ValueError("eval_rung_cohorts: cohorts must share "
                             "(rung_i, epochs)")
    return _eval_cohorts(cohorts, collect_params)


def eval_trial_megabatch(cohorts: List[TrialCohort],
                         collect_params=None) -> List[Tuple[list, list]]:
    """Continuous rung batching (DESIGN.md §13): one fused dispatch for
    cohorts at *different* rungs.

    Same merge semantics as ``eval_rung_cohorts`` — trials tag their job
    slot, data variant, labels, and (for MLP) init key — plus two per-trial
    degrees of freedom from ``TrialCohort.trial_rungs`` / ``trial_steps``:

    - each MLP trial folds its *own* rung cursor into its init key, so a
      rung-0 trial and a rung-2 trial in the same dispatch derive exactly
      the keys their solo runs would;
    - each gradient trial carries its own step budget; the shared Adam scan
      runs ``max(steps)`` slots and shorter trials freeze their carry after
      their own budget (``models.adam_train(n_steps=...)``).

    Both are inert-padding tricks: for the steps a trial actually takes the
    update math is bitwise the sequential path, so an exact-shape megabatch
    is bit-identical to lockstep dispatch and a hetero-shape one matches to
    ~1e-6 (the §12.3 reduction-order caveat).  Returns per-job
    ``(scored, positions)`` pairs in input order."""
    return _eval_cohorts(cohorts, collect_params)


def _eval_cohorts(cohorts: List[TrialCohort],
                  collect_params=None) -> List[Tuple[list, list]]:
    """Shared merge core for ``eval_rung_cohorts``/``eval_trial_megabatch``:
    tags trials (with their own rung cursor and step budget), pads shapes,
    groups sub-batches, and runs the fused dispatch."""
    if collect_params is None:
        collect_params = any(tc.collect for tc in cohorts)
    epochs = max(max(tc.trial_steps) for tc in cohorts)   # scan length
    shapes = [tc.shape for tc in cohorts]
    hetero = len(set(shapes)) > 1
    N_max = max(s[0] for s in shapes)
    Nval_max = max(s[1] for s in shapes)
    d = max(s[2] for s in shapes)
    c = max(s[3] for s in shapes)
    pad_widths = N_max <= WIDTH_PAD_MAX_ROWS

    # register every trial's variant in its own job's cache first (caches
    # persist across rungs), then offset local variant ids into one merged
    # stack: merged vid = job's offset + local vid
    local, stacks = [], []
    for slot, tc in enumerate(cohorts):
        rungs, steps = tc.trial_rungs, tc.trial_steps
        lvids, stack = _variants(tc.ctx, tc.specs)
        stacks.append(stack)
        for pos, spec in enumerate(tc.specs):
            local.append((slot, pos, spec, int(tc.tids[pos]),
                          int(tc.ctx["seed"]), lvids[pos],
                          int(rungs[pos]), int(steps[pos])))
    offsets = np.concatenate([[0], np.cumsum(
        [len(tc.ctx["variant_cache"]) for tc in cohorts])])
    trials = [_TaggedTrial(slot, pos, spec, tid, seed,
                           int(offsets[slot]) + lvid,
                           int(cohorts[slot].ctx["n_classes"]),
                           rung, nsteps)
              for (slot, pos, spec, tid, seed, lvid, rung, nsteps) in local]

    if hetero:
        # per-job stacks go into the fused program unpadded — the trace
        # zero-pads them to the group-maximal shape (``_concat_padded``);
        # labels/masks are host numpy, transferred once inside the jit call.
        # The masks make the padding exactly inert (see module docstring).
        Yall_tr = np.stack([
            np.pad(tc.ctx["y_tr"], (0, N_max - tc.ctx["y_tr"].shape[0]))
            for tc in cohorts])
        Yall_val = np.stack([
            np.pad(tc.ctx["y_val"], (0, Nval_max - tc.ctx["y_val"].shape[0]))
            for tc in cohorts])
        masks = (
            np.stack([(np.arange(N_max) < s[0]).astype(np.float32)
                      for s in shapes]),
            np.stack([(np.arange(Nval_max) < s[1]).astype(np.float32)
                      for s in shapes]),
            np.stack([np.where(np.arange(c) < s[3], 0.0, CLASS_MASK_NEG)
                      .astype(np.float32) for s in shapes]),
        )
    else:
        Yall_tr = jnp.stack([tc.ctx["y_tr_j"] for tc in cohorts])
        Yall_val = jnp.stack([tc.ctx["y_val_j"] for tc in cohorts])
        masks = None
    variants = {}
    for slot, tc in enumerate(cohorts):
        for v in tc.ctx["variant_cache"].values():
            variants[int(offsets[slot]) + v["id"]] = v

    subbatches = _group_subbatches(trials, pad_widths, variants, epochs)
    outs = _eval_rung_fused(tuple(gin for (_i, _d, gin) in subbatches),
                            tuple(s[0] for s in stacks),
                            tuple(s[1] for s in stacks),
                            Yall_tr, Yall_val, masks,
                            descs=tuple(d_ for (_i, d_, _g) in subbatches),
                            c=c, d=d, epochs=epochs)
    evaluated = [(idxs, vaccs, desc.family, params_b)
                 for (idxs, desc, _g), (params_b, vaccs)
                 in zip(subbatches, outs)]
    results = _unpack_results(evaluated, trials, variants, collect_params)

    per_job: List[Tuple[list, list]] = []
    for slot, tc in enumerate(cohorts):
        idxs = [i for i in sorted(results) if trials[i].job == slot]
        scored = [(tc.specs[trials[i].pos],) + results[i] for i in idxs]
        per_job.append((scored, [trials[i].pos for i in idxs]))
    return per_job
