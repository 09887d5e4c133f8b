from photon_ml_tpu.optimize.common import (
    MarginOracle,
    OptimizationResult,
    OptimizerConfig,
    PathConfig,
    ToleranceSchedule,
    parse_tolerance_schedule,
)
from photon_ml_tpu.optimize.lbfgs import lbfgs
from photon_ml_tpu.optimize.owlqn import owlqn
from photon_ml_tpu.optimize.tron import tron


OPTIMIZERS = {"lbfgs": lbfgs, "owlqn": owlqn, "tron": tron}


def get_optimizer(name: str):
    key = name.lower()
    if key not in OPTIMIZERS:
        raise ValueError(f"unknown optimizer '{name}'; known: {sorted(OPTIMIZERS)}")
    return OPTIMIZERS[key]


def run_optimizer(optimizer: str, fg, w0, config, *, l1=None, l1_mask=None,
                  hvp=None, precond=None, margins=None):
    """Run ``optimizer`` on ``fg(w) -> (value, grad)`` from ``w0``, handing
    it the extras it takes and no others: OWL-QN the L1 weight ``l1`` and
    the mask of the coefficients it shrinks (``l1_mask``, None = all); TRON
    the Hessian-vector product ``hvp(w, v)`` (None = autodiff of ``fg``)
    and the preconditioner's diagonal ``precond(w)`` (None = plain CG);
    both ``fg`` in halves that share a point's margins (``margins``, a
    :class:`MarginOracle`, None = ``fg`` alone: TRON's ``hvp`` and
    ``precond`` then read its ``curvature(m)`` in ``w``'s place); L-BFGS
    none of them."""
    opt = get_optimizer(optimizer)
    optimizer = optimizer.lower()
    if optimizer == "owlqn":
        return opt(fg, w0, l1, config, l1_mask=l1_mask, margins=margins)
    if optimizer == "tron":
        return opt(fg, w0, config, hvp=hvp, precond=precond,
                   margins=margins)
    return opt(fg, w0, config)


def __getattr__(name):
    # PathSolver lives behind a lazy hook: optimize/path.py reaches into
    # photon_ml_tpu.parallel (which itself imports this package for the
    # optimizer registry), so importing it eagerly here would be a cycle.
    if name in ("PathSolver", "PathLambdaStats"):
        from photon_ml_tpu.optimize import path

        return getattr(path, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
