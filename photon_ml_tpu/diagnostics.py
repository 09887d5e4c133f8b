"""Model diagnostics — the classic driver's diagnostic stage.

The reference's legacy ``Driver`` ends with a diagnostics stage (SURVEY.md
§3.3: "staged pipeline (... → validate → diagnostics)"): goodness-of-fit
and model-quality reports alongside the trained models. TPU-native
equivalents here:

* ``hosmer_lemeshow``: decile goodness-of-fit test for binary models.
* ``bootstrap_coefficients``: coefficient confidence intervals via
  multinomial-weight bootstrap, run as a **vmap of the jitted L-BFGS fit**
  — R replicate fits execute as one batched XLA program instead of R
  cluster jobs (the TPU answer to the reference's driver-side bootstrap).
* ``feature_importance``: |w_j| * std_j ranking (scale-adjusted weight
  magnitude).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.optimize.lbfgs import lbfgs
from photon_ml_tpu.types import LabeledBatch


def hosmer_lemeshow(
    probabilities: np.ndarray,
    labels: np.ndarray,
    n_bins: int = 10,
) -> Dict[str, float]:
    """Hosmer–Lemeshow chi-square over probability deciles. Returns the
    statistic, degrees of freedom, and p-value (chi2 survival function)."""
    probabilities = np.asarray(probabilities, np.float64)
    labels = np.asarray(labels, np.float64)
    order = np.argsort(probabilities)
    p_sorted = probabilities[order]
    y_sorted = labels[order]
    bins = np.array_split(np.arange(len(p_sorted)), n_bins)
    stat = 0.0
    used = 0
    for idx in bins:
        if len(idx) == 0:
            continue
        exp = float(p_sorted[idx].sum())
        obs = float(y_sorted[idx].sum())
        n = len(idx)
        denom = exp * (1.0 - exp / n)
        if denom <= 0:
            continue
        stat += (obs - exp) ** 2 / denom
        used += 1
    dof = max(used - 2, 1)
    from scipy.stats import chi2

    return {"statistic": stat, "dof": dof, "p_value": float(chi2.sf(stat, dof))}


def bootstrap_coefficients(
    objective: GLMObjective,
    batch: LabeledBatch,
    w_hat: jax.Array,
    l2: float = 0.0,
    n_replicates: int = 32,
    seed: int = 0,
    config: Optional[OptimizerConfig] = None,
    ci: float = 0.95,
) -> Dict[str, np.ndarray]:
    """Percentile confidence intervals for coefficients.

    Bootstrap resampling is expressed as multinomial example weights (the
    weight-space formulation — no data copy), and every replicate warm-starts
    from ``w_hat``; ``vmap`` batches all replicate L-BFGS fits into one XLA
    program."""
    if config is None:
        config = OptimizerConfig(max_iters=50)
    n = batch.num_examples

    @jax.jit
    def run_all(key):
        counts = jax.random.multinomial(
            key, n, jnp.full((n,), 1.0 / n), shape=(n_replicates, n)
        ).astype(batch.weights.dtype)

        def one(boot_counts):
            b = batch.replace(weights=batch.weights * boot_counts)
            res = lbfgs(lambda w: objective.value_and_grad(w, b, l2),
                        w_hat, config)
            return res.w

        return jax.vmap(one)(counts)

    ws = np.asarray(run_all(jax.random.key(seed)))  # [R, d]
    alpha = (1.0 - ci) / 2.0
    return {
        "mean": ws.mean(axis=0),
        "std": ws.std(axis=0, ddof=1),
        "lower": np.quantile(ws, alpha, axis=0),
        "upper": np.quantile(ws, 1.0 - alpha, axis=0),
        "replicates": ws,
    }


def feature_importance(
    w: np.ndarray,
    feature_std: Optional[np.ndarray] = None,
    top_k: Optional[int] = None,
) -> Dict[str, np.ndarray]:
    """Rank features by scale-adjusted coefficient magnitude
    ``|w_j| * std_j`` (plain ``|w_j|`` when no summary is available)."""
    w = np.asarray(w)
    score = np.abs(w) * (np.asarray(feature_std) if feature_std is not None
                         else 1.0)
    order = np.argsort(-score)
    if top_k is not None:
        order = order[:top_k]
    return {"index": order, "score": score[order]}
