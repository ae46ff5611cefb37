"""Bayesian strategy search + persistent strategy cache.

Parity with ATorch's acceleration-engine search
(``auto/engine/sg_algo/bayes_opt_sg.py:1`` HEBO-backed BO strategy
generation, ``auto/engine/acceleration_engine.py:12`` the
ANALYSE→TUNE→DRYRUN task pipeline, ``auto/strategy.py`` strategy
save/load).  TPU-first shape: the search space is the discrete grid of
(mesh factorization × remat policy × grad-accum) Strategy points; the
expensive objective is a **timed dry-run** of the fully compiled SPMD
train step; a small numpy Gaussian-process surrogate with expected-
improvement acquisition picks which points to pay for.  The winner is
persisted in a JSON cache keyed by (model, batch, topology) fingerprints
so elastic restarts skip the search entirely (reference strategy
save/load via ``--save_strategy_path``/``load_strategy``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from dlrover_tpu.common.log import logger
from dlrover_tpu.parallel.mesh import MeshSpec, candidate_specs

# Strategy import is deferred in functions to avoid a cycle with
# accelerate.py (which imports this module for search()).

REMAT_CHOICES = ("none", "dots", "full", "block", "offload")
ACCUM_CHOICES = (1, 2, 4, 8)


# ---------------------------------------------------------------------------
# Strategy (de)serialization — the persistence format
# ---------------------------------------------------------------------------


def strategy_to_dict(strategy) -> dict:
    import jax.numpy as jnp  # local: keep module import light

    return {
        "mesh": {
            a: getattr(strategy.mesh, a)
            for a in ("pp", "dp", "fsdp", "ep", "tp")
        },
        "remat": strategy.remat,
        "compute_dtype": jnp.dtype(strategy.compute_dtype).name,
        "grad_accum": strategy.grad_accum,
        "donate": strategy.donate,
    }


def strategy_from_dict(d: dict):
    import jax.numpy as jnp

    from dlrover_tpu.parallel.accelerate import Strategy

    for key in ("fp8", "quant_grads", "offload_opt"):
        if d.get(key):
            # Stored strategies come from outside the process (a cache
            # file, the master's KV): one scored under an option this
            # tree no longer has is refused, not run without it under
            # its old score.
            raise ValueError(
                f"stored strategy has {key!r}: true; Strategy has no "
                f"{key} option: search again")
    return Strategy(
        mesh=MeshSpec(**d["mesh"]),
        remat=d["remat"],
        compute_dtype=jnp.dtype(d["compute_dtype"]),
        grad_accum=int(d["grad_accum"]),
        donate=bool(d.get("donate", True)),
    )


# ---------------------------------------------------------------------------
# Search space
# ---------------------------------------------------------------------------


def default_space(
    n_devices: int,
    *,
    remat: Sequence[str] = REMAT_CHOICES,
    accum: Sequence[int] = ACCUM_CHOICES,
    allow_ep: bool = False,
    allow_pp: bool = True,
    base=None,
) -> List[Any]:
    """The discrete Strategy grid for ``n_devices`` (the combination half
    of reference ``combination_sg.py`` crossed with tunables).

    Covers every lever the bench sweeps by hand (r2 NOTES "next perf
    wins"): pp factorizations, per-block/offload remat and grad-accum
    up to 8."""
    from dlrover_tpu.parallel.accelerate import Strategy

    base = base or Strategy()
    out = []
    for spec in candidate_specs(
        n_devices, allow_ep=allow_ep, allow_pp=allow_pp
    ):
        for r in remat:
            for a in accum:
                out.append(dataclasses.replace(
                    base, mesh=spec, remat=r, grad_accum=a))
    return out


def estimate_step_hbm_bytes(
    params_shape: Any,
    sample_batch: Any,
    strategy,
    *,
    opt_state_multiplier: float = 2.0,
    d_model_hint: Optional[int] = None,
) -> float:
    """Cheap per-device HBM model for pruning strategies BEFORE the
    expensive compile (reference ``analyser`` static pass feeding
    ``bayes_opt_sg``).  Deliberately coarse — it only needs to reject
    configurations that are OBVIOUSLY over budget:

    - params: f32 master copy sharded over (fsdp*pp) — NOT tp: against
      compiled truth (``tools/calibrate_hbm.py`` vs XLA buffer
      assignment) tp does not reduce peak, because the gathered bf16
      working copies the tp matmuls need erase the sharding's saving
      (observed peak == state/fsdp exactly, with or without tp).
    - optimizer state: ``opt_state_multiplier`` x params
    - gradients: one more params-worth
    - activations: tokens_per_device x d_model x ~24 residual-stream
      copies for remat="none", scaled down by remat policy and
      grad-accum (microbatching divides live activations).
    - the sum is centered by ``_CALIBRATION`` (fit over 14 compiled
      llama_300m/800m points, see CALIBRATE_HBM.json: the raw model
      over-predicted a consistent ~1.35x).
    """
    import jax as _jax

    sizes = [
        int(np.prod(x.shape)) * _dtype_bytes(x)
        for x in _jax.tree_util.tree_leaves(params_shape)
        if hasattr(x, "shape")
    ]
    p_bytes = float(sum(sizes))
    m = strategy.mesh
    model_shards = max(1, m.fsdp) * max(1, m.pp)
    params_dev = 4.0 / _avg_dtype_bytes(params_shape) * p_bytes \
        / model_shards  # master f32 copy
    opt_dev = opt_state_multiplier * params_dev
    grads_dev = params_dev

    batch_leaves = [
        x for x in _jax.tree_util.tree_leaves(sample_batch)
        if hasattr(x, "shape") and np.ndim(x) >= 2
    ]
    tokens = max(
        (int(np.prod(np.shape(x))) for x in batch_leaves), default=0
    )
    data_shards = max(1, m.dp) * max(1, m.fsdp)
    d_model = d_model_hint or _guess_d_model(params_shape)
    act_factor = {
        "none": 24.0, "dots": 8.0, "block": 2.0, "offload": 1.0,
        "full": 1.0,
    }.get(strategy.remat, 8.0)
    acts_dev = (
        tokens / data_shards / max(1, strategy.grad_accum)
        * d_model * 2.0 * act_factor  # bf16 activations
    )
    return _CALIBRATION * (params_dev + opt_dev + grads_dev + acts_dev)


# Fit against compiled.memory_analysis() peak bytes over 14 strategy
# points (llama_300m/800m x dp/fsdp/tp x remat x accum, 8-device mesh;
# tools/calibrate_hbm.py, artifact CALIBRATE_HBM.json): raw-model ratio
# geomean was 1.35 with tp exempted from model_shards.
_CALIBRATION = 0.75


def _dtype_bytes(x) -> int:
    try:
        return int(np.dtype(x.dtype).itemsize)
    except Exception:  # noqa: BLE001
        return 4


def _avg_dtype_bytes(params_shape) -> float:
    import jax as _jax

    bs = [
        _dtype_bytes(x)
        for x in _jax.tree_util.tree_leaves(params_shape)
        if hasattr(x, "dtype")
    ]
    return float(np.mean(bs)) if bs else 4.0


def _guess_d_model(params_shape) -> int:
    """Most common trailing dim among 2-D params — a good-enough proxy
    for the residual width."""
    import jax as _jax
    from collections import Counter

    dims = Counter()
    for x in _jax.tree_util.tree_leaves(params_shape):
        shape = getattr(x, "shape", ())
        if len(shape) == 2:
            dims[int(min(shape))] += 1
    return dims.most_common(1)[0][0] if dims else 1024


def prune_space_by_memory(
    space: Sequence[Any],
    params_shape: Any,
    sample_batch: Any,
    hbm_bytes: float,
    **kw,
) -> List[Any]:
    """Drop strategies whose estimated per-device HBM exceeds the budget
    (keeps everything if that would empty the space — the model is
    coarse and the timed dry-run is the real arbiter)."""
    kept = [
        s for s in space
        if estimate_step_hbm_bytes(params_shape, sample_batch, s, **kw)
        <= hbm_bytes
    ]
    if not kept:
        logger.warning(
            "memory pruning would empty the space (budget %.1f GB); "
            "keeping all %d candidates", hbm_bytes / 1e9, len(space)
        )
        return list(space)
    if len(kept) < len(space):
        logger.info(
            "memory pruning: %d -> %d candidates under %.1f GB",
            len(space), len(kept), hbm_bytes / 1e9,
        )
    return kept


def _features(strategy) -> np.ndarray:
    """Embed a Strategy as a numeric vector for the GP kernel: log2 of the
    mesh factorization + one-hot-ish remat level + log2 accum."""
    m = strategy.mesh
    return np.array(
        [
            np.log2(max(1, m.dp)),
            np.log2(max(1, m.fsdp)),
            np.log2(max(1, m.tp)),
            np.log2(max(1, m.ep)),
            np.log2(max(1, m.pp)),
            float(REMAT_CHOICES.index(strategy.remat))
            if strategy.remat in REMAT_CHOICES
            else 1.0,
            np.log2(max(1, strategy.grad_accum)),
        ],
        dtype=np.float64,
    )


# ---------------------------------------------------------------------------
# Tiny exact GP + expected improvement (minimization)
# ---------------------------------------------------------------------------


class _GP:
    """Exact GP with an RBF kernel on standardized features; a few dozen
    observations at most, so O(n^3) is free."""

    def __init__(self, lengthscale: float = 1.0, noise: float = 1e-4):
        self.ls = lengthscale
        self.noise = noise
        self._X: Optional[np.ndarray] = None

    def _k(self, A: np.ndarray, B: np.ndarray) -> np.ndarray:
        d2 = ((A[:, None, :] - B[None, :, :]) ** 2).sum(-1)
        return np.exp(-0.5 * d2 / (self.ls**2))

    def fit(self, X: np.ndarray, y: np.ndarray) -> None:
        self._X = X
        self._ymean = float(y.mean())
        self._ystd = float(y.std()) or 1.0
        yn = (y - self._ymean) / self._ystd
        K = self._k(X, X) + self.noise * np.eye(len(X))
        self._L = np.linalg.cholesky(K)
        self._alpha = np.linalg.solve(
            self._L.T, np.linalg.solve(self._L, yn)
        )

    def predict(self, Xs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        Ks = self._k(Xs, self._X)
        mu = Ks @ self._alpha
        v = np.linalg.solve(self._L, Ks.T)
        var = np.clip(1.0 - (v**2).sum(0), 1e-12, None)
        return (
            mu * self._ystd + self._ymean,
            np.sqrt(var) * self._ystd,
        )


def _expected_improvement(
    mu: np.ndarray, sigma: np.ndarray, best: float
) -> np.ndarray:
    from scipy.special import ndtr  # Phi

    z = (best - mu) / sigma
    phi = np.exp(-0.5 * z**2) / np.sqrt(2 * np.pi)
    return (best - mu) * ndtr(z) + sigma * phi


@dataclasses.dataclass
class SearchResult:
    best: Any                      # Strategy
    best_cost: float
    evaluated: List[Tuple[Any, float]]  # (Strategy, cost) in eval order
    from_cache: bool = False


class BayesStrategySearch:
    """BO over the discrete strategy grid (reference ``bayes_opt_sg.py``).

    ``objective(strategy) -> cost`` (seconds/step; raise or return ``inf``
    for infeasible points).  ``warm_start`` strategies (e.g. the static
    cost model's pick) are evaluated first, so the search can only match
    or beat them.
    """

    def __init__(
        self,
        objective: Callable[[Any], float],
        space: Sequence[Any],
        *,
        n_init: int = 3,
        max_evals: int = 10,
        warm_start: Sequence[Any] = (),
        seed: int = 0,
    ):
        self.objective = objective
        self.space = list(space)
        self.n_init = n_init
        self.max_evals = max_evals
        self.warm_start = list(warm_start)
        self.rng = np.random.default_rng(seed)

    def run(self) -> SearchResult:
        feats = np.stack([_features(s) for s in self.space])
        fmean = feats.mean(0)
        fstd = feats.std(0)
        fstd[fstd == 0] = 1.0
        feats_n = (feats - fmean) / fstd

        evaluated: List[Tuple[Any, float]] = []
        seen: set = set()

        def key_of(s):
            return json.dumps(strategy_to_dict(s), sort_keys=True)

        def evaluate(idx: int) -> None:
            s = self.space[idx]
            k = key_of(s)
            if k in seen:
                return
            seen.add(k)
            try:
                cost = float(self.objective(s))
            except Exception as e:  # noqa: BLE001 - infeasible point
                logger.info(
                    "strategy search: %s infeasible: %s", s.describe(), e
                )
                cost = float("inf")
            evaluated.append((s, cost))
            logger.info(
                "strategy search: %s -> %.4g s/step", s.describe(), cost
            )

        # 1. Warm starts (the cost model's pick goes here).
        for s in self.warm_start:
            k = key_of(s)
            for i, cand in enumerate(self.space):
                if key_of(cand) == k:
                    evaluate(i)
                    break
            else:
                # Warm start outside the grid: evaluate it directly.
                if k not in seen:
                    seen.add(k)
                    try:
                        cost = float(self.objective(s))
                    except Exception:  # noqa: BLE001
                        cost = float("inf")
                    evaluated.append((s, cost))

        # 2. Random init to seed the surrogate.
        order = self.rng.permutation(len(self.space))
        for i in order:
            if sum(1 for _ in evaluated) >= self.n_init + len(
                self.warm_start
            ):
                break
            evaluate(int(i))

        # 3. BO loop: fit GP on finite observations, maximize EI.
        while len(evaluated) < self.max_evals and len(seen) < len(
            self.space
        ):
            obs = [
                (s, c) for s, c in evaluated if np.isfinite(c)
            ]
            remaining = [
                i for i, s in enumerate(self.space)
                if key_of(s) not in seen
            ]
            if not remaining:
                break
            if len(obs) < 2:
                evaluate(int(self.rng.choice(remaining)))
                continue
            X = np.stack(
                [(_features(s) - fmean) / fstd for s, _ in obs]
            )
            y = np.array([c for _, c in obs])
            gp = _GP()
            try:
                gp.fit(X, y)
            except np.linalg.LinAlgError:
                evaluate(int(self.rng.choice(remaining)))
                continue
            mu, sigma = gp.predict(feats_n[remaining])
            ei = _expected_improvement(mu, sigma, float(y.min()))
            evaluate(remaining[int(np.argmax(ei))])

        finite = [(s, c) for s, c in evaluated if np.isfinite(c)]
        if not finite:
            raise RuntimeError("strategy search: every candidate failed")
        best, best_cost = min(finite, key=lambda sc: sc[1])
        logger.info(
            "strategy search: best %s (%.4g s/step) after %d evals",
            best.describe(), best_cost, len(evaluated),
        )
        return SearchResult(
            best=best, best_cost=best_cost, evaluated=evaluated
        )


# ---------------------------------------------------------------------------
# Persistent strategy cache
# ---------------------------------------------------------------------------


def fingerprint(
    params_shape: Any, batch: Any, n_devices: int, opt_shape: Any = None
) -> str:
    """Stable key for (model, optimizer, batch, topology): hashes the
    flattened param/opt-state/batch shapes+dtypes and the device count.
    The optimizer state matters — a strategy tuned for SGD's memory
    profile is wrong for Adam's 3x state."""
    import jax

    def leaf_sig(leaf) -> str:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return f"{tuple(leaf.shape)}:{leaf.dtype}"
        return f"{tuple(np.shape(leaf))}:{np.asarray(leaf).dtype}"

    parts: List[str] = [f"ndev={n_devices}"]
    parts += [leaf_sig(x) for x in jax.tree_util.tree_leaves(params_shape)]
    parts.append("|opt|")
    if opt_shape is not None:
        parts += [leaf_sig(x) for x in jax.tree_util.tree_leaves(opt_shape)]
    parts.append("|batch|")
    parts += [leaf_sig(x) for x in jax.tree_util.tree_leaves(batch)]
    return hashlib.sha1("/".join(parts).encode()).hexdigest()[:16]


class StrategyCache:
    """JSON-file cache: fingerprint -> winning strategy dict (reference
    strategy persistence, ``auto/strategy.py`` save/load)."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()

    def _load(self) -> Dict[str, dict]:
        try:
            with open(self.path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def get(self, key: str):
        with self._lock:
            d = self._load().get(key)
        if d is None:
            return None
        try:
            return strategy_from_dict(d)
        except Exception:  # noqa: BLE001 - stale/corrupt entry
            return None

    def put(self, key: str, strategy) -> None:
        with self._lock:
            data = self._load()
            data[key] = strategy_to_dict(strategy)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            os.makedirs(
                os.path.dirname(os.path.abspath(self.path)), exist_ok=True
            )
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)


class MasterStrategyCache:
    """Strategy cache backed by the job master's KV store (the service
    half of the reference's acceleration engine, ``auto/engine/
    servicer.py``: strategies outlive any one node).  A worker relaunched
    on a *fresh* host — no local JSON file — still skips the search
    because the winning strategy lives with the master."""

    PREFIX = "strategy-cache/"

    def __init__(self, master_client):
        self.client = master_client

    def get(self, key: str):
        try:
            raw = self.client.kv_store_get(self.PREFIX + key)
        except Exception:  # noqa: BLE001 - master unreachable
            return None
        if not raw:
            return None
        try:
            return strategy_from_dict(json.loads(raw.decode()))
        except Exception:  # noqa: BLE001 - stale/corrupt entry
            return None

    def put(self, key: str, strategy) -> None:
        try:
            self.client.kv_store_set(
                self.PREFIX + key,
                json.dumps(strategy_to_dict(strategy)).encode(),
            )
        # graftcheck: disable=CC104 -- strategy-cache write is
        # best-effort: a miss only costs the next job a re-search
        except Exception:  # noqa: BLE001 - cache write is best-effort
            pass
