"""A search-based AutoML engine ``A(D, y) -> M*`` in JAX.

Pipeline configuration = (preprocessor, feature-selector, model family, HPs);
the full search-space tables live in DESIGN.md §10.1.  The engine runs random
sampling + successive halving on the ``epochs`` resource (rung/keep_frac
semantics: DESIGN.md §10.2), under a trial or wall-clock budget, and returns
the best pipeline by validation accuracy — our stand-in for Auto-Sklearn/TPOT
(DESIGN.md §5.4).

Two execution backends share one rung loop (``AutoMLConfig.backend``):

- ``"batched"`` (default): the whole rung cohort is padded/stacked into
  struct-of-arrays params and advanced by per-family ``jax.vmap``-ed training
  in ``automl/batched.py`` — one jitted ``lax.scan`` per family sub-batch
  instead of one per trial (DESIGN.md §10.3).
- ``"loop"``: the sequential reference path, one ``train_model`` call per
  trial.  Kept for parity testing; same-seed runs produce the same winner
  because both backends derive per-trial PRNG keys from
  ``(seed, trial_id, rung)`` rather than evaluation order.

Successive-halving promotion is an on-device top-k mask (``sh_promote``)
applied identically by both backends.

The paper's fine-tuning step (§3.4) maps to ``restrict_family=...``: a
restricted, much shorter search that only considers pipelines using the same
model family as the intermediate configuration M'.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.jaxprof import note_trace
from .models import FAMILIES, accuracy, train_model

__all__ = [
    "AutoMLConfig", "AutoMLResult", "automl_fit", "PipelineSpec",
    "apply_pipeline", "sh_promote", "SearchState", "search_init",
    "search_cohort", "search_record", "search_result", "search_eval_rung",
    "TrialCohort", "search_trial_cohort", "register_backend", "get_backend",
    "available_backends", "BACKENDS", "search_snapshot", "search_restore",
]

# preprocessor and feature-fraction axes of the pipeline search space
# (DESIGN.md §10.1)
PREPROCS = ("none", "standardize", "minmax")
FEATURE_FRACS = (1.0, 0.5)


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """One point of the pipeline search space (DESIGN.md §10.1)."""
    preproc: str        # one of PREPROCS
    feature_frac: float  # one of FEATURE_FRACS (variance-ranked top-k columns)
    family: str         # key into models.FAMILIES
    hp: tuple           # sorted (k, v) tuple from the family's hp_grid


@dataclasses.dataclass
class AutoMLResult:
    spec: PipelineSpec
    params: Any
    val_acc: float
    test_acc: Optional[float]
    time_s: float
    n_trials: int
    feat_idx: np.ndarray
    pre_stats: Dict[str, np.ndarray]
    trials: List[tuple]        # (spec, val_acc), cohort order per rung
    rung_times: List[float] = dataclasses.field(default_factory=list)
    backend: str = "batched"


@dataclasses.dataclass(frozen=True)
class AutoMLConfig:
    """Budget + schedule of one ``automl_fit`` search (DESIGN.md §10.2).

    Every field is anchored in the docs; see DESIGN.md §10 for the full
    execution model.
    """
    n_trials: int = 24                       # sampled population size (§10.2)
    time_budget_s: Optional[float] = None    # wall-clock cutoff (paper §4.1 budgets)
    rungs: Sequence[int] = (20, 60, 180)     # successive-halving epoch rungs (§10.2)
    keep_frac: float = 0.34                  # survivor fraction per rung (§10.2)
    val_frac: float = 0.2                    # holdout fraction scored by accuracy (§5.4)
    seed: int = 0                            # PRNG seed; trial keys fold in (id, rung)
    backend: str = "batched"                 # "batched" (§10.3) | "loop" (reference)


def _fit_preproc(name: str, X: np.ndarray) -> Dict[str, np.ndarray]:
    if name == "standardize":
        return {"mu": X.mean(0), "sd": X.std(0) + 1e-9}
    if name == "minmax":
        return {"lo": X.min(0), "hi": X.max(0)}
    return {}


def _apply_preproc(name: str, stats, X: np.ndarray) -> np.ndarray:
    if name == "standardize":
        return (X - stats["mu"]) / stats["sd"]
    if name == "minmax":
        rng = np.maximum(stats["hi"] - stats["lo"], 1e-9)
        return (X - stats["lo"]) / rng * 2.0 - 1.0
    return X


def _select_features(frac: float, ctx) -> np.ndarray:
    """The ``frac`` share of ``ctx["X_tr"]``'s columns of largest variance
    (cheap, label-free).  The variances are float64, computed once per
    search: in float32, same-scale columns of nearly equal variance swap
    places, and with them the order of the model's weight rows."""
    d = ctx["X_tr"].shape[1]
    k = max(1, int(round(frac * d)))
    if k >= d:
        return np.arange(d)
    if "feature_var" not in ctx:
        ctx["feature_var"] = np.asarray(ctx["X_tr"], np.float64).var(axis=0)
    return np.argsort(-ctx["feature_var"])[:k]


def apply_pipeline(spec: PipelineSpec, pre_stats, feat_idx, X: np.ndarray) -> jnp.ndarray:
    Xp = _apply_preproc(spec.preproc, pre_stats, X)
    return jnp.asarray(Xp[:, feat_idx], dtype=jnp.float32)


def _trial_key(seed: int, trial_id: int, rung_i: int) -> jax.Array:
    """Per-trial PRNG key, independent of evaluation order.

    Both backends derive keys from ``(seed, trial_id, rung)`` so the batched
    cohort and the sequential loop train bit-identical trajectories for the
    same sampled population (DESIGN.md §10.4)."""
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), trial_id), rung_i)


@functools.partial(jax.jit, static_argnames=("keep",))
def _promote_mask(val_acc, *, keep: int):
    note_trace("engine._promote_mask")   # body runs only while tracing
    order = jnp.argsort(-val_acc, stable=True)
    return jnp.zeros(val_acc.shape, bool).at[order[:keep]].set(True)


def sh_promote(val_acc, keep_frac: float) -> jax.Array:
    """Successive-halving promotion as an on-device top-k survivor mask.

    Keeps ``max(1, ceil(n * keep_frac))`` trials; ties broken toward the
    lower trial index (stable sort), matching the sequential reference
    semantics (DESIGN.md §10.2)."""
    val_acc = np.asarray(val_acc, np.float32)
    keep = max(1, int(np.ceil(val_acc.shape[0] * keep_frac)))
    return _promote_mask(val_acc, keep=keep)


def _sample_specs(rng: np.random.Generator, n: int, families: Sequence[str]) -> List[PipelineSpec]:
    specs = []
    for _ in range(n):
        fam = families[rng.integers(len(families))]
        grid = FAMILIES[fam].hp_grid
        hp = tuple(sorted((k, v[rng.integers(len(v))]) for k, v in grid.items()))
        specs.append(
            PipelineSpec(
                preproc=PREPROCS[rng.integers(len(PREPROCS))],
                feature_frac=FEATURE_FRACS[rng.integers(len(FEATURE_FRACS))],
                family=fam,
                hp=hp,
            )
        )
    # dedup, keep order
    seen, out = set(), []
    for s in specs:
        key = (s.preproc, s.feature_frac, s.family, s.hp)
        if key not in seen:
            seen.add(key)
            out.append(s)
    return out


def _eval_rung_loop(cohort, tids, rung_i, epochs, ctx, out_of_budget,
                    collect_params=True):
    """Sequential reference: one ``train_model`` call per trial.

    Returns ``(scored, positions)`` like ``batched.eval_rung_batched``
    (params come for free here, so ``collect_params`` is ignored)."""
    scored = []
    for spec, tid in zip(cohort, tids):
        if out_of_budget() and scored:
            break
        ckey = (spec.preproc, spec.feature_frac)
        if ckey not in ctx["pipe_cache"]:
            stats = _fit_preproc(spec.preproc, ctx["X_tr"])
            fidx = _select_features(spec.feature_frac, ctx)
            Xtr_p = apply_pipeline(spec, stats, fidx, ctx["X_tr"])
            Xval_p = apply_pipeline(spec, stats, fidx, ctx["X_val"])
            ctx["pipe_cache"][ckey] = (stats, fidx, Xtr_p, Xval_p)
        stats, fidx, Xtr_p, Xval_p = ctx["pipe_cache"][ckey]
        params = train_model(
            _trial_key(ctx["seed"], tid, rung_i),
            Xtr_p, ctx["y_tr_j"], spec.family, ctx["n_classes"], dict(spec.hp), epochs,
        )
        vacc = accuracy(params, Xval_p, ctx["y_val_j"], spec.family)
        scored.append((spec, vacc, params, fidx, stats))
    return scored, list(range(len(scored)))


# ---------------------------------------------------------------------------
# SearchBackend registry: "how one rung of trials is evaluated"
# ---------------------------------------------------------------------------

# A backend is a rung evaluator:
#   (cohort, tids, rung_i, epochs, ctx, out_of_budget, collect_params)
#     -> (scored, positions)
# where ``scored[i]`` is the loop-backend tuple
# ``(spec, val_acc, params, feat_idx, pre_stats)`` and ``positions[i]`` its
# index into ``cohort``.  ``AutoMLConfig.backend`` and Plan backends resolve
# through this registry, so third parties can plug in their own evaluator
# (distributed, quantized, ...) without touching the engine (DESIGN.md §12.2).
BACKENDS: Dict[str, Any] = {}


def register_backend(name: str, eval_rung, *, overwrite: bool = False):
    """Register a SearchBackend rung evaluator under ``name``."""
    if not overwrite and name in BACKENDS:
        raise ValueError(f"backend {name!r} already registered "
                         f"(pass overwrite=True to replace)")
    BACKENDS[name] = eval_rung
    return eval_rung


def available_backends():
    return tuple(sorted(BACKENDS))


def get_backend(name: str):
    """Look up a registered backend; unknown names list what exists."""
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown AutoML backend {name!r}; available backends: "
            f"{', '.join(available_backends())}") from None


def _eval_rung_batched_lazy(cohort, tids, rung_i, epochs, ctx, out_of_budget,
                            collect_params=True):
    # deferred import: batched.py imports engine helpers (no cycle at load)
    from .batched import eval_rung_batched
    return eval_rung_batched(cohort, tids, rung_i, epochs, ctx, out_of_budget,
                             collect_params)


register_backend("loop", _eval_rung_loop)
register_backend("batched", _eval_rung_batched_lazy)


@dataclasses.dataclass
class SearchState:
    """Resumable state of one successive-halving search (DESIGN.md §11.3).

    ``automl_fit`` drives a state rung-by-rung to completion; the service
    scheduler instead advances many states in lockstep so compatible rung
    cohorts from different jobs can merge into one batched dispatch
    (``automl/batched.eval_rung_cohorts``).  The cycle per rung is
    ``search_cohort`` (what to evaluate) → any backend evaluation →
    ``search_record`` (promotion + advance); ``search_result`` finalizes.
    """
    config: AutoMLConfig
    classes: np.ndarray                # original label values, sorted
    ctx: dict                          # backend evaluation context
    specs: List[PipelineSpec]
    alive_ids: List[int]
    t_start: float
    rung_i: int = 0
    live: List[tuple] = dataclasses.field(default_factory=list)
    trials_log: List[tuple] = dataclasses.field(default_factory=list)
    rung_times: List[float] = dataclasses.field(default_factory=list)
    n_done: int = 0
    stopped: bool = False              # budget cutoff fired after a rung
    # per-trial rung cursors (DESIGN.md §13.2): ``trial_rung[tid]`` is the
    # rung the trial trains *next*.  Within one search every live trial sits
    # at ``rung_i`` (SH promotion needs the whole cohort scored before
    # anyone advances), but the cursors are what a megabatch dispatch reads:
    # trials from *different* searches carry different cursors into one
    # standing dispatch (``batched.eval_trial_megabatch``), and a culled
    # trial's cursor simply stops advancing — it has left the megabatch.
    trial_rung: Dict[int, int] = dataclasses.field(default_factory=dict)

    @property
    def done(self) -> bool:
        return self.stopped or self.rung_i >= len(self.config.rungs)

    def out_of_budget(self) -> bool:
        return (
            self.config.time_budget_s is not None
            and time.perf_counter() - self.t_start > self.config.time_budget_s
        )


def search_init(
    X: np.ndarray,
    y: np.ndarray,
    *,
    config: AutoMLConfig = AutoMLConfig(),
    restrict_family: Optional[str] = None,
    seed_trials: Optional[Sequence[PipelineSpec]] = None,
) -> SearchState:
    """Build the evaluation context and sample the initial population.

    ``seed_trials`` is the meta-learning warm-start hook (DESIGN.md §17.4):
    when given, rung 0 runs *only* those specs instead of the whole sampled
    population.  The sampled population depends only on ``config.seed``
    (never on the data), so a seed spec that matches a sampled one keeps
    the sampled trial id — and with it the exact ``(seed, trial_id, rung)``
    PRNG key a cold run would use, making the warm trial's accuracy at
    every rung bit-identical to the corresponding cold trial's.  Seed specs
    outside the population are appended with fresh ids (still
    deterministic).  ``seed_trials=None`` (or empty) is byte-for-byte the
    pre-warm-start cold path."""
    get_backend(config.backend)   # unknown names raise, listing the registry
    t_start = time.perf_counter()
    X = np.asarray(X, dtype=np.float32)
    y = np.asarray(y)
    classes, y_enc = np.unique(y, return_inverse=True)
    n_classes = len(classes)
    rng = np.random.default_rng(config.seed)

    # train/val split
    N = X.shape[0]
    perm = rng.permutation(N)
    n_val = max(1, int(config.val_frac * N))
    val_idx, tr_idx = perm[:n_val], perm[n_val:]
    X_tr, y_tr = X[tr_idx], y_enc[tr_idx]
    X_val, y_val = X[val_idx], y_enc[val_idx]

    families = [restrict_family] if restrict_family else list(FAMILIES)
    n_seed_trials = config.n_trials if not restrict_family else max(4, config.n_trials // 4)
    specs = _sample_specs(rng, n_seed_trials, families)
    alive_ids = list(range(len(specs)))
    if seed_trials:
        # warm start: keep only the seeded specs alive.  Matches inherit the
        # sampled trial id (bit-identical PRNG trajectory vs the cold run);
        # novel specs append after the population with fresh ids.
        index = {s: i for i, s in enumerate(specs)}
        ids = []
        for s in seed_trials:
            i = index.get(s)
            if i is None:
                specs.append(s)
                i = len(specs) - 1
                index[s] = i
            ids.append(i)
        alive_ids = sorted(set(ids))

    ctx = {
        "X_tr": X_tr, "y_tr": y_tr, "X_val": X_val, "y_val": y_val,
        "y_tr_j": jnp.asarray(y_tr), "y_val_j": jnp.asarray(y_val),
        "n_classes": n_classes, "seed": config.seed,
        "budget_active": config.time_budget_s is not None,
        "pipe_cache": {},      # loop backend: (preproc, frac) -> projected data
        "variant_cache": {},   # batched backend: (preproc, frac) -> full-width variant
    }
    return SearchState(
        config=config, classes=classes, ctx=ctx, specs=specs,
        alive_ids=alive_ids, t_start=t_start,
        trial_rung={i: 0 for i in alive_ids},
    )


def search_cohort(state: SearchState):
    """Current rung's work unit: ``(cohort, tids, epochs, collect_params)``.

    ``collect_params`` is False on non-final rungs (promotion only needs
    accuracies) — unless a time budget could make this rung the last one
    evaluated."""
    config = state.config
    cohort = [state.specs[i] for i in state.alive_ids]
    collect = (state.rung_i == len(config.rungs) - 1
               or config.time_budget_s is not None)
    return cohort, list(state.alive_ids), int(config.rungs[state.rung_i]), collect


class TrialCohort(NamedTuple):
    """One job's current rung as a uniform, mergeable unit of trial work.

    Every search emits ``TrialCohort``s regardless of which strategy found
    its subset or which backend evaluates it — this is the currency the
    scheduler's cross-job merge layers trade in: same-shaped cohorts fuse
    exactly, differently-shaped ones fuse through maximal-shape padding
    (DESIGN.md §12.3), and cohorts sitting at *different* rungs fuse through
    per-trial step masks (``batched.eval_trial_megabatch``, §13).

    ``rungs``/``steps`` carry each trial's rung cursor and remaining epoch
    budget (from ``SearchState.trial_rung``); the scalar ``rung_i``/
    ``epochs`` remain the uniform-rung view used by the same-rung merge
    entry (``eval_rung_cohorts``) and the lockstep scheduler buckets."""
    specs: list            # PipelineSpec per live trial
    tids: list             # trial ids (PRNG key derivation)
    rung_i: int
    epochs: int
    collect: bool          # params wanted (final rung / budget active)
    ctx: dict              # the SearchState evaluation context
    rungs: tuple = ()      # per-trial rung cursors (§13.2)
    steps: tuple = ()      # per-trial epoch budgets at those cursors

    @property
    def shape(self):
        """(N_tr, N_val, d, n_classes) — the merge-compatibility axes."""
        return (self.ctx["X_tr"].shape[0], self.ctx["X_val"].shape[0],
                self.ctx["X_tr"].shape[1], self.ctx["n_classes"])

    @property
    def trial_rungs(self):
        """Per-trial rungs, defaulting to the uniform ``rung_i``."""
        return self.rungs if self.rungs else (self.rung_i,) * len(self.specs)

    @property
    def trial_steps(self):
        """Per-trial step budgets, defaulting to the uniform ``epochs``."""
        return self.steps if self.steps else (self.epochs,) * len(self.specs)


def search_trial_cohort(state: SearchState) -> TrialCohort:
    """The current rung of ``state`` as a ``TrialCohort``."""
    cohort, tids, epochs, collect = search_cohort(state)
    rungs = tuple(state.trial_rung.get(t, state.rung_i) for t in tids)
    steps = tuple(int(state.config.rungs[r]) for r in rungs)
    return TrialCohort(cohort, tids, state.rung_i, epochs, collect, state.ctx,
                       rungs, steps)


def search_record(state: SearchState, scored, positions, rung_time: float) -> None:
    """Record one evaluated rung: log trials, promote survivors, advance.

    ``scored``/``positions`` are the backend's rung output (loop-backend
    tuple layout).  Promotion is the on-device top-k mask shared by both
    backends; survivors keep population order except under a time budget
    (DESIGN.md §10.2)."""
    config = state.config
    state.rung_times.append(rung_time)
    state.trials_log.extend((s, v) for (s, v, *_rest) in scored)
    state.n_done += len(scored)
    state.live = scored
    # on-device top-k promotion; survivors keep population order — except
    # under a time budget, where the next rung runs best-first so a
    # mid-rung cutoff spends the remaining budget on the strongest trials
    mask = np.asarray(sh_promote(
        np.asarray([v for (_s, v, *_r) in scored], np.float32), config.keep_frac))
    surv = list(np.flatnonzero(mask))
    if config.time_budget_s is not None:
        surv.sort(key=lambda i: (-scored[i][1], i))
    state.alive_ids = [state.alive_ids[positions[i]] for i in surv]
    state.rung_i += 1
    # survivors' cursors advance to the next rung; culled trials keep their
    # last cursor — they have left the standing megabatch (DESIGN.md §13.2)
    for tid in state.alive_ids:
        state.trial_rung[tid] = state.rung_i
    if state.out_of_budget():
        state.stopped = True


def search_result(
    state: SearchState,
    X_test: Optional[np.ndarray] = None,
    y_test: Optional[np.ndarray] = None,
) -> AutoMLResult:
    """Finalize: pick the accuracy-argmax of the last evaluated rung."""
    live = state.live
    best_i = int(np.argmax([v for (_s, v, *_r) in live]))  # ties -> lower index
    best_spec, best_vacc, best_params, best_fidx, best_stats = live[best_i]
    if callable(best_params):   # batched backend materializes params lazily
        best_params = best_params()
    test_acc = None
    if X_test is not None:
        Xt = apply_pipeline(best_spec, best_stats, best_fidx, np.asarray(X_test, np.float32))
        yt = jnp.asarray(np.searchsorted(state.classes, np.asarray(y_test)))
        test_acc = accuracy(best_params, Xt, yt, best_spec.family)

    return AutoMLResult(
        spec=best_spec,
        params=best_params,
        val_acc=float(best_vacc),
        test_acc=test_acc,
        time_s=time.perf_counter() - state.t_start,
        n_trials=state.n_done,
        feat_idx=best_fidx,
        pre_stats=best_stats,
        trials=state.trials_log,
        rung_times=state.rung_times,
        backend=state.config.backend,
    )


# context keys that cross process boundaries; the jnp mirrors and the
# per-backend caches are derived state, rebuilt on restore
_CTX_SNAPSHOT_KEYS = ("X_tr", "y_tr", "X_val", "y_val", "n_classes", "seed",
                      "budget_active")


def _materialize_scored(scored):
    """Resolve the batched backend's lazy param thunks into real pytrees so
    a scored rung can cross a process boundary (DESIGN.md §14.2)."""
    out = []
    for spec, vacc, params, fidx, stats in scored:
        if callable(params):
            params = params()
        out.append((spec, float(vacc), params, fidx, stats))
    return out


def search_snapshot(state: SearchState) -> dict:
    """A wire-serializable snapshot of one search (DESIGN.md §14.4).

    Captures exactly the state ``search_restore`` needs to continue the
    search bit-identically in another process: the config, the sampled
    population and survivor cursors, the trial log, and the raw evaluation
    data.  Derived device state (jnp label mirrors, the pipe/variant
    caches) is dropped and rebuilt — it is a pure function of the data, so
    resuming reproduces the uninterrupted run exactly.  Lazy param thunks
    in the last scored rung are materialized (wire refuses callables)."""
    ctx = state.ctx
    return {
        "config": state.config,
        "classes": np.asarray(state.classes),
        "specs": list(state.specs),
        "alive_ids": [int(i) for i in state.alive_ids],
        "rung_i": int(state.rung_i),
        "live": _materialize_scored(state.live),
        "trials_log": [(s, float(v)) for s, v in state.trials_log],
        "rung_times": [float(t) for t in state.rung_times],
        "n_done": int(state.n_done),
        "stopped": bool(state.stopped),
        "trial_rung": {int(k): int(v) for k, v in state.trial_rung.items()},
        "elapsed_s": time.perf_counter() - state.t_start,
        "ctx": {k: ctx[k] for k in _CTX_SNAPSHOT_KEYS},
    }


def search_restore(snap: dict) -> SearchState:
    """Rebuild a ``SearchState`` from a ``search_snapshot`` payload.

    The restored search continues from the exact rung boundary the
    snapshot captured; finishing it produces the same winner spec and the
    same trial accuracies as the uninterrupted run (tested across a real
    process boundary in tests/test_wire.py)."""
    ctx = dict(snap["ctx"])
    ctx["X_tr"] = np.asarray(ctx["X_tr"], np.float32)
    ctx["X_val"] = np.asarray(ctx["X_val"], np.float32)
    ctx["y_tr"] = np.asarray(ctx["y_tr"])
    ctx["y_val"] = np.asarray(ctx["y_val"])
    ctx["y_tr_j"] = jnp.asarray(ctx["y_tr"])
    ctx["y_val_j"] = jnp.asarray(ctx["y_val"])
    ctx["n_classes"] = int(ctx["n_classes"])
    ctx["seed"] = int(ctx["seed"])
    ctx["budget_active"] = bool(ctx["budget_active"])
    ctx["pipe_cache"] = {}
    ctx["variant_cache"] = {}
    return SearchState(
        config=snap["config"],
        classes=np.asarray(snap["classes"]),
        ctx=ctx,
        specs=list(snap["specs"]),
        alive_ids=[int(i) for i in snap["alive_ids"]],
        t_start=time.perf_counter() - float(snap["elapsed_s"]),
        rung_i=int(snap["rung_i"]),
        live=[tuple(t) for t in snap["live"]],
        trials_log=[tuple(t) for t in snap["trials_log"]],
        rung_times=list(snap["rung_times"]),
        n_done=int(snap["n_done"]),
        stopped=bool(snap["stopped"]),
        trial_rung={int(k): int(v) for k, v in snap["trial_rung"].items()},
    )


def search_eval_rung(state: SearchState):
    """Evaluate the current rung in-process (single-job path) and record it.

    The service scheduler bypasses this for batched jobs it can merge
    (``automl/batched.eval_rung_cohorts``); everything else — ``automl_fit``,
    loop-backend jobs, time-budgeted jobs — rungs through here."""
    _eval_rung = get_backend(state.config.backend)
    cohort, tids, epochs, collect = search_cohort(state)
    t_rung = time.perf_counter()
    scored, positions = _eval_rung(cohort, tids, state.rung_i, epochs, state.ctx,
                                   state.out_of_budget, collect)
    search_record(state, scored, positions, time.perf_counter() - t_rung)


def automl_fit(
    X: np.ndarray,
    y: np.ndarray,
    *,
    config: AutoMLConfig = AutoMLConfig(),
    restrict_family: Optional[str] = None,
    X_test: Optional[np.ndarray] = None,
    y_test: Optional[np.ndarray] = None,
) -> AutoMLResult:
    """Run the AutoML search.  Returns the best pipeline found.

    ``restrict_family`` implements the paper's restricted fine-tune pass.
    This is the one-shot driver over the resumable ``SearchState`` API
    (``search_init``/``search_cohort``/``search_record``/``search_result``)
    that the service scheduler uses to interleave many searches."""
    state = search_init(X, y, config=config, restrict_family=restrict_family)
    # successive halving over epoch rungs: each rung retrains the surviving
    # cohort from scratch at the next epoch budget (DESIGN.md §10.2)
    while not state.done:
        search_eval_rung(state)
    return search_result(state, X_test, y_test)
