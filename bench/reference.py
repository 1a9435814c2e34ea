"""The plain reference: each answer a tenant received, recomputed.

Independent of the program: it imports nothing from ``src/`` and takes
nothing the program made except the answers under test.  For one job it
recomputes, from the job's own table and for the choices the job reported:

- **Gen-DST** — the subset's fitness, ``-|H(D[r, c]) - H(D)|`` with H the
  mean over columns of the Shannon entropy (log2) of the factorized codes
  (SubStrat Def. 3.4), for the rows ``r`` and columns ``c`` the job returned
  (target column included);
- **sub-AutoML** — every logged trial retrained on the subset from its own
  initial state: its validation accuracy against the logged one; and the
  winner, as its last rung trains it: the class probabilities its
  parameters give the validation rows, against those of the parameters the
  job returned;
- **fine-tune** — the winner retrained on the full training split: the
  class probabilities, as for the sub-AutoML winner.

The search space, the sampling of the population, the train/validation
split, the preprocessors and full-batch Adam follow the program's
documented semantics (DESIGN.md §10); they are rewritten here in plain
``jax.numpy``: entropies in float64, training at float32 with JAX's
default matmul precision, which is the program's.  ``mode="control"``
computes all of it in bfloat16: that is the control, which, put in the
program's place, has to fail; so has ``mode="frozen"``, whose training
steps leave the state unchanged.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["factorize_codes", "subset_fitness", "answers", "served_answers",
           "compare", "faults", "prob_gap", "trial_accuracies", "MODES",
           "FAMILIES", "population"]

# compute type of the training arithmetic per mode: the program's float32
# (at JAX's default matmul precision, as the program runs it); bfloat16, the
# control; and "frozen", a fault: float32 whose training steps return the
# state they were given
MODES = {"reference": jnp.float32, "control": jnp.bfloat16,
         "frozen": jnp.float32}

# the pipeline search space: family -> hyper-parameter grid (dict order is
# the sampling order), preprocessors and feature fractions
FAMILIES = {
    "logreg": {"lr": (0.3, 0.1, 0.03), "l2": (0.0, 1e-4, 1e-2)},
    "mlp": {"lr": (0.01, 0.003, 0.001), "l2": (0.0, 1e-4),
            "width": (32, 64, 128), "depth": (1, 2)},
    "linear_svm": {"lr": (0.1, 0.03, 0.01), "l2": (1e-4, 1e-2)},
    "gnb": {"var_smoothing": (1e-9, 1e-6, 1e-3)},
    "centroid": {"shrinkage": (0.0, 0.2, 0.5)},
}
PREPROCS = ("none", "standardize", "minmax")
FEATURE_FRACS = (1.0, 0.5)


# ---------------------------------------------------------------------------
# Gen-DST: factorized codes and entropy fitness
# ---------------------------------------------------------------------------


def factorize_codes(X, y, max_bins: int = 256, categorical: int = 64):
    """(N, M+1) integer codes: a column with at most ``categorical``
    distinct values keeps one code per value, a denser one is cut into
    ``max_bins`` quantile bins (empty bins dropped); the label is the last
    column, one code per class."""
    cols = [np.asarray(X[:, j], np.float64) for j in range(X.shape[1])]
    cols.append(np.asarray(y, np.float64))
    codes = np.empty((len(y), len(cols)), np.int64)
    for j, col in enumerate(cols):
        uniq, inv = np.unique(col, return_inverse=True)
        if len(uniq) <= max(categorical, 2) or j == len(cols) - 1:
            codes[:, j] = inv
        else:
            qs = np.quantile(col, np.linspace(0.0, 1.0, max_bins + 1)[1:-1])
            binned = np.searchsorted(qs, col, side="right")
            codes[:, j] = np.unique(binned, return_inverse=True)[1]
    return codes


def _entropies(codes, mode):
    """Per-column entropy in bits: float64, or bfloat16 for the control."""
    out = []
    for j in range(codes.shape[1]):
        counts = np.bincount(codes[:, j]).astype(np.float64)
        counts = counts[counts > 0]
        if mode == "control":
            c = jnp.asarray(counts, jnp.bfloat16)
            p = c / jnp.sum(c)
            out.append(float(-jnp.sum(p * jnp.log2(p))))
        else:
            p = counts / counts.sum()
            out.append(float(-np.sum(p * np.log2(p))))
    return np.asarray(out)


def subset_fitness(codes, row_idx, col_idx, mode="reference") -> float:
    """``-|H(D[r, c]) - H(D)|``; ``col_idx`` lists the subset's feature
    columns, the label column is always in the subset."""
    cols = sorted(set(int(c) for c in col_idx) | {codes.shape[1] - 1})
    h_full = _entropies(codes, mode).mean()
    h_sub = _entropies(codes[np.asarray(row_idx)][:, cols], mode).mean()
    return float(-abs(h_sub - h_full))


# ---------------------------------------------------------------------------
# AutoML: population, pipelines, training
# ---------------------------------------------------------------------------


def population(rng, n: int, families):
    """Sample ``n`` pipeline specs (deduplicated, in order) as hashable
    ``(preproc, feature_frac, family, hp_items)`` tuples."""
    out, seen = [], set()
    for _ in range(n):
        fam = families[rng.integers(len(families))]
        hp = tuple(sorted((k, v[rng.integers(len(v))])
                          for k, v in FAMILIES[fam].items()))
        spec = (PREPROCS[rng.integers(len(PREPROCS))],
                FEATURE_FRACS[rng.integers(len(FEATURE_FRACS))], fam, hp)
        if spec not in seen:
            seen.add(spec)
            out.append(spec)
    return out


def spec_key(s: dict):
    return (s["preproc"], float(s["feature_frac"]), s["family"],
            tuple(sorted((k, v) for k, v in s["hp"])))


class Search:
    """One search's data as the program sees it: labels encoded, the
    validation hold-out drawn, the population sampled from the seed."""

    def __init__(self, X, y, cfg: dict, restrict=None):
        X = np.asarray(X, np.float64)
        self.classes, y_enc = np.unique(np.asarray(y), return_inverse=True)
        self.c = len(self.classes)
        rng = np.random.default_rng(cfg["seed"])
        perm = rng.permutation(len(y_enc))
        n_val = max(1, int(cfg["val_frac"] * len(y_enc)))
        self.X_tr, self.y_tr = X[perm[n_val:]], y_enc[perm[n_val:]]
        self.X_val, self.y_val = X[perm[:n_val]], y_enc[perm[:n_val]]
        fams = [restrict] if restrict else list(FAMILIES)
        n = cfg["n_trials"] if not restrict else max(4, cfg["n_trials"] // 4)
        self.specs = population(rng, n, fams)
        self.seed = cfg["seed"]

    def pipeline(self, spec):
        """``(transform, feature columns)`` fitted on the training part."""
        pre, frac = spec[0], spec[1]
        X = self.X_tr
        if pre == "standardize":
            mu, sd = X.mean(0), X.std(0) + 1e-9
            f = lambda Z: (Z - mu) / sd
        elif pre == "minmax":
            lo, span = X.min(0), np.maximum(X.max(0) - X.min(0), 1e-9)
            f = lambda Z: (Z - lo) / span * 2.0 - 1.0
        else:
            f = lambda Z: Z
        d = X.shape[1]
        k = max(1, int(round(frac * d)))
        cols = np.arange(d) if k >= d else np.argsort(-X.var(axis=0))[:k]
        return (lambda Z: f(np.asarray(Z, np.float64))[:, cols]), cols


def _dot(a, b):
    return jnp.matmul(a, b)


def _logits(family, params, X):
    if family == "mlp":
        h = X
        for i, (w, b) in enumerate(params):
            h = _dot(h, w) + b
            if i < len(params) - 1:
                h = jnp.maximum(h, 0)
        return h
    if family == "gnb":
        mu, var, prior = params
        ll = -0.5 * (((X[:, None, :] - mu[None]) ** 2) / var[None]
                     + jnp.log(2 * jnp.pi * var)[None]).sum(-1)
        return ll + prior[None]
    if family == "centroid":
        return -((X[:, None, :] - params[None]) ** 2).sum(-1)
    w, b = params
    return _dot(X, w) + b


def _loss(family, params, X, y, l2):
    lg = _logits(family, params, X)
    if family == "linear_svm":
        correct = jnp.take_along_axis(lg, y[:, None], axis=1)
        margins = jnp.maximum(0.0, lg - correct + 1.0)
        margins = margins * (1 - jax.nn.one_hot(y, lg.shape[1], dtype=lg.dtype))
        data = margins.sum(axis=1).mean()
    else:
        logp = jax.nn.log_softmax(lg)
        data = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
    ws = [w for w, _ in params] if family == "mlp" else [params[0]]
    return data + l2 * sum(jnp.sum(w ** 2) for w in ws)


@functools.partial(jax.jit, static_argnames=("family", "epochs"))
def _adam(family, params, X, y, lr, l2, epochs: int):
    grad = jax.grad(lambda p: _loss(family, p, X, y, l2))
    zeros = jax.tree.map(jnp.zeros_like, params)

    def step(t, carry):
        p, m, v = carry
        g = grad(p)
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        tc = (t + 1).astype(jnp.float32)
        c1, c2 = 1 - 0.9 ** tc, 1 - 0.999 ** tc
        p = jax.tree.map(
            lambda a, mm, vv: (a - lr * (mm / c1) / (jnp.sqrt(vv / c2) + 1e-8)
                               ).astype(a.dtype), p, m, v)
        return p, m, v

    return jax.lax.fori_loop(0, epochs, step, (params, zeros, zeros))[0]


def _closed_fit(family, X, y, c, hp, dt):
    onehot = jax.nn.one_hot(y, c, dtype=dt)
    cnt = onehot.sum(0)[:, None]
    mean = _dot(onehot.T, X) / jnp.maximum(cnt, 1.0)
    if family == "gnb":
        sq = _dot(onehot.T, X ** 2) / jnp.maximum(cnt, 1.0)
        var = jnp.maximum(sq - mean ** 2, 0.0) + hp["var_smoothing"]
        prior = jnp.log(jnp.maximum(cnt[:, 0] / X.shape[0], 1e-12))
        return mean, var, prior
    overall = X.mean(0, keepdims=True)
    return overall + (mean - overall) * (1.0 - hp["shrinkage"])


def train(spec, tid: int, rung: int, epochs: int, X, y, c: int, seed: int,
          mode):
    """Train one trial from its own initial state; returns its params."""
    family, hp = spec[2], dict(spec[3])
    dt = MODES[mode]
    X, y = jnp.asarray(X, dt), jnp.asarray(y)
    if family in ("gnb", "centroid"):
        return _closed_fit(family, X, y, c, hp, dt)
    d = X.shape[1]
    if family == "mlp":
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed),
                                                    tid), rung)
        dims = [d] + [int(hp["width"])] * int(hp["depth"]) + [c]
        keys = jax.random.split(key, len(dims) - 1)
        params = tuple(
            ((jax.random.normal(k, (dims[i], dims[i + 1]))
              * (2.0 / dims[i]) ** 0.5).astype(dt), jnp.zeros(dims[i + 1], dt))
            for i, k in enumerate(keys))
    else:
        params = (jnp.zeros((d, c), dt), jnp.zeros((c,), dt))
    if mode == "frozen":
        return params
    return _adam(family, params, X, y, jnp.asarray(hp["lr"], dt),
                 jnp.asarray(hp["l2"], dt), epochs)


def accuracy(spec, params, X, y, mode) -> float:
    lg = _logits(spec[2], params, jnp.asarray(X, MODES[mode]))
    return float(np.mean(np.asarray(jnp.argmax(lg, axis=1)) == np.asarray(y)))


def rung_sizes(total: int, n_rungs: int, keep_frac: float):
    """Trials per rung of a successive-halving log of ``total`` entries."""
    for n0 in range(1, total + 1):
        sizes, n = [], n0
        for _ in range(n_rungs):
            sizes.append(n)
            n = max(1, math.ceil(n * keep_frac))
        if sum(sizes) == total:
            return sizes
    raise ValueError(f"no successive-halving schedule logs {total} trials")


def named_params(family, params) -> dict:
    """The reference's parameters under the program's leaf names."""
    if family == "mlp":
        out = {}
        for i, (w, b) in enumerate(params):
            out[f"layers.{i}.w"], out[f"layers.{i}.b"] = w, b
        return out
    if family == "gnb":
        return dict(zip(("mean", "var", "prior"), params))
    if family == "centroid":
        return {"cent": params}
    return {"w": params[0], "b": params[1]}


def served_params(tree) -> dict:
    """A served model's parameters (nested dicts and lists) by leaf name."""
    out = {}
    for k, v in tree.items():
        if k == "layers":
            for i, layer in enumerate(v):
                for kk, vv in layer.items():
                    out[f"layers.{i}.{kk}"] = np.asarray(vv, np.float64)
        else:
            out[k] = np.asarray(v, np.float64)
    return out


# ---------------------------------------------------------------------------
# the whole job
# ---------------------------------------------------------------------------


def trial_accuracies(search: Search, trials, cfg: dict, mode) -> list:
    """Every logged trial (``[spec, val_acc]``, rung by rung) retrained from
    its own initial state in ``mode``: its validation accuracy."""
    ids = {s: i for i, s in enumerate(search.specs)}
    sizes = rung_sizes(len(trials), len(cfg["rungs"]), cfg["keep_frac"])
    out, pos = [], 0
    for rung, size in enumerate(sizes):
        for spec_d, _acc in trials[pos:pos + size]:
            spec = spec_key(spec_d)
            tf, _cols = search.pipeline(spec)
            params = train(spec, ids[spec], rung, int(cfg["rungs"][rung]),
                           tf(search.X_tr), search.y_tr, search.c,
                           search.seed, mode)
            out.append(accuracy(spec, params, tf(search.X_val),
                                search.y_val, mode))
        pos += size
    return out


def _winner(search: Search, spec_d: dict, cfg: dict, mode):
    """A search's winner retrained as the program trains it (its own trial
    id, the last rung's cursor and epochs): ``{"spec", "params" (by leaf
    name), "X_val"}`` with the validation rows as the winner's pipeline
    transforms them, or None where the spec is not in the population."""
    spec = spec_key(spec_d)
    if spec not in search.specs:
        return None
    tf, _cols = search.pipeline(spec)
    last = len(cfg["rungs"]) - 1
    params = train(spec, search.specs.index(spec), last,
                   int(cfg["rungs"][last]), tf(search.X_tr), search.y_tr,
                   search.c, search.seed, mode)
    return {"spec": spec, "params": named_params(spec[2], params),
            "X_val": tf(search.X_val)}


def answers(job: dict, X, y, plan: dict, *, sub_pass: bool,
            mode="reference") -> dict:
    """What one job answers, recomputed from its table in ``mode`` for the
    choices the job made (its subset and its two winners): the subset's
    fitness, the sub-AutoML winner (``None`` without a sub pass or where
    the reference cannot repeat it) with the validation accuracy of every
    logged trial, and the fine-tune winner (``None`` where its spec is not
    in its population), each winner as ``_winner`` gives it."""
    res = job["result"]
    rows, cols = res["row_idx"], res["col_idx"]
    out = {"fitness": subset_fitness(factorize_codes(X, y), rows, cols, mode),
           "sub": None, "ft": None}
    if sub_pass:
        Xs, ys = np.asarray(X)[rows][:, cols], np.asarray(y)[rows]
        if len(np.unique(ys)) == len(np.unique(y)):
            # (a subset that misses a class is patched by a key-seeded draw
            # the reference does not repeat; its winner goes unchecked)
            search = Search(Xs, ys, plan["sub_automl"])
            out["sub"] = _winner(search, res["intermediate"]["spec"],
                                 plan["sub_automl"], mode)
            out["sub_trials"] = trial_accuracies(
                search, res["intermediate"]["trials"], plan["sub_automl"],
                mode)
            out["sub_val_rows"] = len(search.y_val)
    final = res["final"]
    out["ft"] = _winner(
        Search(X, y, plan["fine_tune"], restrict=final["spec"]["family"]),
        final["spec"], plan["fine_tune"], mode)
    return out


def served_answers(job: dict) -> dict:
    res = job["result"]
    return {"fitness": res["dst_fitness"],
            "sub": {"params": served_params(res["intermediate"]["params"])},
            "sub_trials": [v for _s, v in res["intermediate"]["trials"]],
            "ft": {"params": served_params(res["final"]["params"])}}


def _unnamed(family, named: dict):
    """``named_params``'s inverse: the reference's parameter structure."""
    leaf = {k: jnp.asarray(np.asarray(v, np.float32)) for k, v in named.items()}
    if family == "mlp":
        return tuple((leaf[f"layers.{i}.w"], leaf[f"layers.{i}.b"])
                     for i in range(len(leaf) // 2))
    if family == "gnb":
        return (leaf["mean"], leaf["var"], leaf["prior"])
    if family == "centroid":
        return leaf["cent"]
    return (leaf["w"], leaf["b"])


def prob_gap(got: dict, want: dict) -> float:
    """Mean over the winner's validation rows of the total-variation
    distance between the class probabilities (softmax of the logits) of
    ``got``'s parameters and of the reference's; 1.0 where the leaves or
    their shapes differ.  Probabilities, not parameters: directions the
    loss cannot see (a constant added to every class's logit) move under
    Adam by round-off alone, and change no answer."""
    spec, ref = want["spec"], want["params"]
    params = got["params"]
    if set(params) != set(ref) or any(np.shape(params[k]) != np.shape(ref[k])
                                      for k in ref):
        return 1.0
    X = jnp.asarray(want["X_val"], jnp.float32)
    p = jax.nn.softmax(_logits(spec[2], _unnamed(spec[2], params), X))
    q = jax.nn.softmax(_logits(spec[2], _unnamed(spec[2], ref), X))
    return float(0.5 * jnp.mean(jnp.sum(jnp.abs(p - q), axis=1)))


def compare(got: dict, ref: dict) -> dict:
    """The numbers that hold ``got`` (a job's answers, or the control's)
    against the reference's ``ref``: ``fitness_gap``; where the reference
    has a sub-AutoML winner, ``sub_prob_gap`` and ``sub_trials_off``, the
    share of logged trials whose validation accuracy lies more than one
    and a half validation rows from the reference's; ``ft_prob_gap``."""
    out = {"fitness_gap": abs(got["fitness"] - ref["fitness"])}
    if ref["sub"] is not None:
        out["sub_prob_gap"] = (1.0 if got["sub"] is None
                               else prob_gap(got["sub"], ref["sub"]))
        # one validation row is what rounding flips; two or more is another
        # model
        row = 1.0 / ref["sub_val_rows"]
        gaps = [abs(a - b) for a, b in zip(got["sub_trials"],
                                           ref["sub_trials"])]
        out["sub_trials_off"] = sum(g > 1.5 * row for g in gaps) / len(gaps)
    out["ft_prob_gap"] = (1.0 if ref["ft"] is None or got["ft"] is None
                          else prob_gap(got["ft"], ref["ft"]))
    return out


def faults(job: dict, X, y, plan: dict, *, sub_pass: bool,
           winner_family=None) -> list:
    """Answers that break an exact rule: the subset's shape, the
    sub-AutoML winner is the best of its last rung, the fine-tune keeps the
    winner's family and draws its winner from its own population."""
    res, out = job["result"], []
    n, m = plan["dst_rows"], plan["dst_cols"]
    rows, cols = res["row_idx"], res["col_idx"]
    if len(rows) != n or len(set(cols)) != m - 1 or not all(
            0 <= r < len(y) for r in rows) or not all(
            0 <= c < X.shape[1] for c in cols):
        out.append("subset shape")
    inter, final = res["intermediate"], res["final"]
    if sub_pass:
        cfg = plan["sub_automl"]
        last = rung_sizes(len(inter["trials"]), len(cfg["rungs"]),
                          cfg["keep_frac"])[-1]
        if inter["val_acc"] != max(v for _s, v in inter["trials"][-last:]):
            out.append("sub-AutoML winner is not the best of its last rung")
        winner_family = inter["spec"]["family"]
    if final["spec"]["family"] != winner_family:
        out.append("fine-tune left the winner's family")
    return out
