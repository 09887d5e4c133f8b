"""Optimizer tests vs scipy/sklearn ground truth on convex problems
(the reference's optimizer unit tier: known convex problems, SURVEY.md §8)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optimize import OptimizerConfig, lbfgs, owlqn, tron
from photon_ml_tpu.types import make_batch


def _logreg_problem(rng, n=200, d=10, l2=1.0):
    X = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ w_true))).astype(float)
    batch = make_batch(jnp.asarray(X), y, dtype=jnp.float64)
    obj = make_objective("logistic")
    fg = lambda w: obj.value_and_grad(w, batch, l2)
    # scipy reference solution
    def f_np(w):
        m = X @ w
        return np.sum(np.logaddexp(0, m) - y * m) + 0.5 * l2 * w @ w
    def g_np(w):
        m = X @ w
        return X.T @ (1 / (1 + np.exp(-m)) - y) + l2 * w
    ref = scipy.optimize.minimize(f_np, np.zeros(d), jac=g_np, method="L-BFGS-B",
                                  options={"ftol": 1e-14, "gtol": 1e-10})
    return fg, obj, batch, X, y, ref, l2


def test_lbfgs_matches_scipy(rng):
    fg, obj, batch, X, y, ref, l2 = _logreg_problem(rng)
    res = lbfgs(fg, jnp.zeros(X.shape[1]), OptimizerConfig(max_iters=200, tolerance=1e-10))
    assert bool(res.converged)
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-8)
    np.testing.assert_allclose(res.w, ref.x, rtol=1e-4, atol=1e-5)
    # history recorded, monotone-ish decreasing, NaN-padded after `iterations`
    it = int(res.iterations)
    hist = np.asarray(res.loss_history)
    assert np.all(np.isfinite(hist[:it])) and np.all(np.isnan(hist[it:]))
    assert hist[it - 1] <= hist[0] + 1e-12


def test_lbfgs_jits_and_quadratic_exact(rng):
    A = rng.normal(size=(12, 8))
    Q = A.T @ A + 0.5 * np.eye(8)
    b = rng.normal(size=8)
    fun = lambda w: (0.5 * w @ jnp.asarray(Q) @ w - jnp.asarray(b) @ w,
                     jnp.asarray(Q) @ w - jnp.asarray(b))
    run = jax.jit(lambda w0: lbfgs(fun, w0, OptimizerConfig(max_iters=100, tolerance=1e-12)))
    res = run(jnp.zeros(8))
    np.testing.assert_allclose(res.w, np.linalg.solve(Q, b), rtol=1e-6, atol=1e-8)


def test_tron_matches_scipy(rng):
    fg, obj, batch, X, y, ref, l2 = _logreg_problem(rng)
    res = tron(fg, jnp.zeros(X.shape[1]), OptimizerConfig(max_iters=100, tolerance=1e-10))
    assert bool(res.converged)
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-9)
    np.testing.assert_allclose(res.w, ref.x, rtol=1e-4, atol=1e-6)


def test_tron_poisson(rng):
    n, d = 150, 6
    X = rng.normal(size=(n, d)) * 0.5
    w_true = rng.normal(size=d) * 0.5
    y = rng.poisson(np.exp(X @ w_true)).astype(float)
    batch = make_batch(jnp.asarray(X), y, dtype=jnp.float64)
    obj = make_objective("poisson")
    fg = lambda w: obj.value_and_grad(w, batch, 0.5)
    res = tron(fg, jnp.zeros(d), OptimizerConfig(max_iters=100, tolerance=1e-10))
    def f_np(w):
        m = X @ w
        return np.sum(np.exp(m) - y * m) + 0.25 * w @ w
    ref = scipy.optimize.minimize(f_np, np.zeros(d), method="L-BFGS-B",
                                  options={"ftol": 1e-14, "gtol": 1e-10})
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-8)


def test_owlqn_matches_sklearn_l1(rng):
    from sklearn.linear_model import LogisticRegression

    n, d = 300, 12
    X = rng.normal(size=(n, d))
    w_true = np.where(rng.random(d) < 0.5, 0.0, rng.normal(size=d))
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ w_true))).astype(float)
    l1 = 3.0
    batch = make_batch(jnp.asarray(X), y, dtype=jnp.float64)
    obj = make_objective("logistic")
    fg = lambda w: obj.value_and_grad(w, batch, 0.0)
    res = owlqn(fg, jnp.zeros(d), l1, OptimizerConfig(max_iters=300, tolerance=1e-9))
    # sklearn liblinear: C = 1/l1 (sum-loss convention), no intercept
    sk = LogisticRegression(penalty="l1", C=1.0 / l1, solver="liblinear",
                            fit_intercept=False, tol=1e-10, max_iter=5000)
    sk.fit(X, y)
    w_sk = sk.coef_.ravel()
    F = lambda w: float(obj.value(jnp.asarray(w), batch, 0.0)) + l1 * np.abs(w).sum()
    # objective value parity (coefficients may differ slightly at equal loss)
    assert F(np.asarray(res.w)) <= F(w_sk) * (1 + 1e-5)
    # sparsity: recovered support should be sparse like sklearn's
    assert (np.abs(np.asarray(res.w)) < 1e-8).sum() > 0
    np.testing.assert_allclose(np.asarray(res.w), w_sk, atol=5e-3)


def test_owlqn_zero_l1_equals_lbfgs(rng):
    fg, obj, batch, X, y, ref, l2 = _logreg_problem(rng)
    res = owlqn(fg, jnp.zeros(X.shape[1]), 0.0, OptimizerConfig(max_iters=200, tolerance=1e-10))
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-7)


def test_elastic_net_via_owlqn_plus_l2(rng):
    # elastic net = L2 folded into smooth objective + L1 via OWL-QN
    from photon_ml_tpu.ops.regularization import RegularizationContext, RegularizationType

    ctx = RegularizationContext(RegularizationType.ELASTIC_NET, alpha=0.4)
    lam = 2.0
    assert np.isclose(ctx.l1_weight(lam), 0.8)
    assert np.isclose(ctx.l2_weight(lam), 1.2)
    n, d = 100, 5
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)
    batch = make_batch(jnp.asarray(X), y, dtype=jnp.float64)
    obj = make_objective("logistic")
    fg = lambda w: obj.value_and_grad(w, batch, ctx.l2_weight(lam))
    res = owlqn(fg, jnp.zeros(d), ctx.l1_weight(lam), OptimizerConfig(max_iters=200))
    assert bool(res.converged)
    assert np.isfinite(float(res.value))


def test_line_search_failure_at_optimum_reports_converged(rng):
    """Starting AT the minimizer, the first line search cannot make
    progress (zero/tiny gradient); that must report converged=True via
    the gradient test, not a stall — and never a spurious relative-loss
    'convergence' from the unchanged f."""
    n, d = 300, 8
    X = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ w_true))).astype(float)
    batch = make_batch(jnp.asarray(X), y, dtype=jnp.float64)
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=200, tolerance=1e-10)

    fg = lambda w: obj.value_and_grad(w, batch, 1.0)
    first = lbfgs(fg, jnp.zeros(d, jnp.float64), cfg)
    assert bool(first.converged)
    # restart from the solution: immediate gradient-test convergence
    again = lbfgs(fg, first.w, cfg)
    assert bool(again.converged)
    assert int(again.iterations) <= 2
    # it may take one more tiny productive step before the gradient
    # test fires; the point must stay at the same optimum
    np.testing.assert_allclose(np.asarray(again.w), np.asarray(first.w),
                               rtol=1e-5, atol=1e-7)


def test_tron_jacobi_preconditioner(rng):
    """Jacobi-preconditioned TRON: same optimum, far fewer outer
    iterations on a badly-scaled problem (each CG step in the distributed
    setting is a full data pass, so this is the cost that matters)."""
    from photon_ml_tpu.optimize.tron import tron

    n, d = 2000, 40
    scales = np.logspace(-2, 2, d)
    X = rng.normal(size=(n, d)) * scales
    w_true = rng.normal(size=d) / scales
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ w_true))).astype(float)
    batch = make_batch(jnp.asarray(X), y, dtype=jnp.float64)
    obj = make_objective("logistic")
    fg = lambda w: obj.value_and_grad(w, batch, 1.0)
    hvp = lambda w, v: obj.hvp(w, v, batch, 1.0)
    diag = lambda w: obj.diagonal_hessian(w, batch, 1.0)
    cfg = OptimizerConfig(max_iters=100, tolerance=1e-10)

    plain = tron(fg, jnp.zeros(d, jnp.float64), cfg, hvp=hvp)
    prec = tron(fg, jnp.zeros(d, jnp.float64), cfg, hvp=hvp, precond=diag)
    assert bool(prec.converged)
    assert int(prec.iterations) < int(plain.iterations)
    np.testing.assert_allclose(float(prec.value), float(plain.value),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(prec.w), np.asarray(plain.w),
                               rtol=1e-2, atol=1e-4)


@pytest.mark.parametrize("name", ["lbfgs", "owlqn", "tron"])
def test_run_optimizer_is_the_direct_call(rng, name):
    """``run_optimizer`` hands each optimizer the extras it takes and no
    others: the result is the direct call's, bit for bit."""
    from photon_ml_tpu.optimize import run_optimizer

    fg, obj, batch, X, *_ = _logreg_problem(rng)
    w0 = jnp.zeros(X.shape[1], jnp.float64)
    cfg = OptimizerConfig(max_iters=30, tolerance=1e-9)
    mask = jnp.ones_like(w0).at[0].set(0.0)
    hvp = lambda w, v: obj.hvp(w, v, batch, 1.0)
    diag = lambda w: obj.diagonal_hessian(w, batch, 1.0)
    want = {
        "lbfgs": lambda: lbfgs(fg, w0, cfg),
        "owlqn": lambda: owlqn(fg, w0, 0.7, cfg, l1_mask=mask),
        "tron": lambda: tron(fg, w0, cfg, hvp=hvp, precond=diag),
    }[name]()
    got = run_optimizer(name, fg, w0, cfg, l1=0.7, l1_mask=mask, hvp=hvp,
                        precond=diag)
    assert int(got.iterations) == int(want.iterations) > 1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- TRON's second-order oracle at an iterate, once (ISSUE 37) ---------------
def _margin_oracle(fg, margins, curvature):
    """``fg`` in halves: the value beside the margins ``margins(w)``, the
    gradient, and the curvature of the margins."""
    from photon_ml_tpu.optimize import MarginOracle

    return MarginOracle(value=lambda w: (fg(w)[0], margins(w)),
                        grad=lambda w, m: fg(w)[1], curvature=curvature)


def _poisson_oracles(rng, n=400, d=12, l2=1.0):
    """A dense Poisson problem whose first TRON step is refused (counts in
    the tens make ``|g0|`` too wide a radius for ``exp``), with its oracle
    twice: from ``w`` (``hvp_w``, ``diag_w``) and from the curvature vector
    ``d2 = exp(m)`` of the margins ``m = X w`` (``oracle``, a
    ``MarginOracle``, and ``hvp_c``, ``diag_c``)."""
    X = jnp.asarray(rng.normal(size=(n, d)) * 0.5)
    y = jnp.asarray(rng.poisson(30.0 * np.exp(
        np.asarray(X) @ (rng.normal(size=d) * 0.5))).astype(float))
    batch = make_batch(X, np.asarray(y), dtype=jnp.float64)
    obj = make_objective("poisson")
    fg = lambda w: obj.value_and_grad(w, batch, l2)
    hvp_c = lambda d2, v: X.T @ (d2 * (X @ v)) + l2 * v
    diag_c = lambda d2: (X * X).T @ d2 + l2
    return dict(
        fg=fg, w0=jnp.zeros(d, jnp.float64),
        hvp_w=lambda w, v: hvp_c(jnp.exp(X @ w), v),
        diag_w=lambda w: diag_c(jnp.exp(X @ w)),
        oracle=_margin_oracle(fg, lambda w: X @ w, jnp.exp),
        hvp_c=hvp_c, diag_c=diag_c)


def _accepted(res, p):
    """-> [steps] bool: the steps of a ``tolerance=0`` fit of problem ``p``
    that moved the loss (a refused step hands back the loss before it, to
    the bit)."""
    history = np.asarray(res.loss_history)[:int(res.iterations)]
    return np.diff(history, prepend=float(p["fg"](p["w0"])[0])) != 0


def test_tron_with_curvature_is_tron_without(rng):
    """The HVP and the diagonal handed the oracle's curvature of the
    margins are those handed ``w``: the same arithmetic on one ``d2`` where
    there was one a call."""
    p = _poisson_oracles(rng)
    cfg = OptimizerConfig(max_iters=8, tolerance=0.0)
    without = tron(p["fg"], p["w0"], cfg, hvp=p["hvp_w"], precond=p["diag_w"])
    with_c = tron(p["fg"], p["w0"], cfg, hvp=p["hvp_c"], precond=p["diag_c"],
                  margins=p["oracle"])
    assert int(without.rejected_steps) >= 1  # both branches of the cond
    assert int(without.cg_steps) > int(without.iterations) == 8
    for name in ("iterations", "cg_steps", "rejected_steps",
                 "precond_passes", "gather_products"):
        assert int(getattr(with_c, name)) == int(getattr(without, name)), name
    np.testing.assert_allclose(np.asarray(with_c.w), np.asarray(without.w),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(with_c.loss_history),
                               np.asarray(without.loss_history), rtol=1e-12)
    assert int(without.curvature_passes) == int(without.margins_reused) == 0
    assert int(with_c.curvature_passes) == int(with_c.precond_passes)
    # every curvature but w0's is read off an accepted trial's margins
    assert int(with_c.margins_reused) == int(with_c.curvature_passes) - 1 > 0


@pytest.mark.parametrize("precond", [False, True])
def test_refused_step_keeps_the_curvature_of_the_kept_w(rng, precond):
    """With ``w`` itself for the margins and the identity for the
    curvature, the state's ``c`` IS the iterate the oracle believes it
    stands at. The fit is then the plain one bit for bit only if, after a
    refused step as after an accepted one, ``c`` (and with it the
    diagonal) is that of the kept ``w``."""
    p = _poisson_oracles(rng)
    cfg = OptimizerConfig(max_iters=8, tolerance=0.0)
    diag = p["diag_w"] if precond else None
    plain = tron(p["fg"], p["w0"], cfg, hvp=p["hvp_w"], precond=diag)
    carried = tron(p["fg"], p["w0"], cfg, hvp=p["hvp_w"], precond=diag,
                   margins=_margin_oracle(p["fg"], lambda w: w, lambda m: m))
    refused = ~_accepted(plain, p)
    assert refused[0] and not refused.all()
    assert int(plain.rejected_steps) == refused.sum()
    counters = dict(curvature_passes=None, margins_reused=None)
    for a, b in zip(jax.tree.leaves(carried._replace(**counters)),
                    jax.tree.leaves(plain._replace(**counters))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("steps", [1, 2, 6])
def test_second_order_passes_stop_before_the_last_iterate(rng, steps):
    """One curvature and one diagonal at ``w0``, and one of each for every
    step that is accepted and followed by another iteration: what the last
    iteration would compute no CG solve reads."""
    p = _poisson_oracles(rng)
    res = tron(p["fg"], p["w0"],
               OptimizerConfig(max_iters=steps, tolerance=0.0),
               hvp=p["hvp_c"], precond=p["diag_c"], margins=p["oracle"])
    assert int(res.iterations) == steps
    want = 1 + int(_accepted(res, p)[:-1].sum())
    assert int(res.precond_passes) == int(res.curvature_passes) == want
    if steps == 6:
        assert 1 < want < 6  # a refused step and accepted ones among them
    for counter in (res.precond_passes, res.curvature_passes):
        assert counter.dtype == jnp.int32 and counter.shape == ()
    # the diagonal alone follows the same rule
    alone = tron(p["fg"], p["w0"],
                 OptimizerConfig(max_iters=steps, tolerance=0.0),
                 hvp=p["hvp_w"], precond=p["diag_w"])
    assert (int(alone.precond_passes), int(alone.curvature_passes)) == (
        want, 0)


def test_converged_fit_computes_no_curvature_of_its_last_iterate(rng):
    fg, obj, batch, X, y, ref, l2 = _logreg_problem(rng)
    Xj = jnp.asarray(X)
    sig = jax.nn.sigmoid
    res = tron(fg, jnp.zeros(X.shape[1], jnp.float64),
               OptimizerConfig(max_iters=100, tolerance=1e-10),
               hvp=lambda d2, v: Xj.T @ (d2 * (Xj @ v)) + l2 * v,
               precond=lambda d2: (Xj * Xj).T @ d2 + l2,
               margins=_margin_oracle(fg, lambda w: Xj @ w,
                                      lambda m: sig(m) * sig(-m)))
    assert bool(res.converged) and int(res.rejected_steps) == 0
    # every step accepted; the converging one renews nothing
    assert int(res.curvature_passes) == int(res.iterations) > 1
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-9)
    np.testing.assert_allclose(res.w, ref.x, rtol=1e-4, atol=1e-6)


def test_curvature_needs_an_explicit_hvp(rng):
    p = _poisson_oracles(rng)
    with pytest.raises(ValueError, match="curvature"):
        tron(p["fg"], p["w0"], margins=p["oracle"])


def test_run_optimizer_hands_tron_the_curvature(rng):
    from photon_ml_tpu.optimize import run_optimizer

    p = _poisson_oracles(rng)
    cfg = OptimizerConfig(max_iters=4, tolerance=0.0)
    kw = dict(hvp=p["hvp_c"], precond=p["diag_c"], margins=p["oracle"])
    want = tron(p["fg"], p["w0"], cfg, **kw)
    got = run_optimizer("tron", p["fg"], p["w0"], cfg, **kw)
    assert int(got.curvature_passes) == int(want.curvature_passes) >= 1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # L-BFGS takes none of it and counts none of it
    plain = run_optimizer("lbfgs", p["fg"], p["w0"], cfg, **kw)
    assert plain.curvature_passes is None and plain.margins_reused is None


# -- the backtracking search hands back what its accepted trial computed -----
def _search_problem():
    """A quadratic and a descent direction whose first trial (alpha 1)
    overshoots: its second, alpha 1/2, is accepted. ``aux`` of a point is a
    vector its evaluation computes beside the value (OWL-QN's margins)."""
    A = jnp.asarray(np.random.default_rng(5).normal(size=(7, 4)))

    def fun(w):
        m = A @ w
        return jnp.sum((m - 1.0) ** 2), m

    w = jnp.zeros(4)
    g = jax.grad(lambda w: fun(w)[0])(w)
    # along -s g the quadratic passes Armijo up to alpha ~ 2 / 3
    s = 3.0 * (g @ g) / (g @ (2.0 * A.T @ (A @ g)))
    return fun, w, -s * g, g


def test_search_hands_back_the_aux_of_the_trial_it_accepts():
    from photon_ml_tpu.optimize.linesearch import backtracking

    fun, w, p, g = _search_problem()
    f0, m0 = fun(w)
    w_new, f_new, m_new, n, ok = jax.jit(
        lambda w: backtracking(fun, w, p, f0, g, aux0=m0))(w)
    assert bool(ok) and int(n) == 2  # it backtracked once
    np.testing.assert_array_equal(np.asarray(w_new), np.asarray(w + 0.5 * p))
    f_want, m_want = jax.jit(fun)(w_new)
    np.testing.assert_array_equal(np.asarray(m_new), np.asarray(m_want))
    assert float(f_new) == float(f_want)
    # without ``aux0`` ``fun`` returns the value alone: the same search
    plain = jax.jit(lambda w: backtracking(lambda x: fun(x)[0], w, p, f0,
                                           g))(w)
    assert plain[2] is None
    for a, b in ((plain[0], w_new), (plain[1], f_new), (plain[3], n)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_failed_search_hands_back_the_kept_points_aux():
    """An ascent direction: no trial passes, ``w`` is kept, and so is the
    ``aux`` the caller handed in, not the last trial's."""
    from photon_ml_tpu.optimize.linesearch import backtracking

    fun, w, p, g = _search_problem()
    f0, _ = fun(w)
    kept = jnp.full((7,), 7.0)  # anything but a trial's margins
    w_new, f_new, m_new, n, ok = jax.jit(lambda w: backtracking(
        fun, w, -p, f0, g, max_evals=3, aux0=kept))(w)
    assert not bool(ok) and int(n) == 3
    np.testing.assert_array_equal(np.asarray(w_new), np.asarray(w))
    assert float(f_new) == float(f0)
    np.testing.assert_array_equal(np.asarray(m_new), np.asarray(kept))


# -- the (s, y) history: one owner of its layout (optimize/common.py) --------
HISTORY_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "history_parity_pr35.npz")
M = 4  # history slots in the two-loop tests


def _pairs(rng, n, d):
    """``n`` curvature pairs with ``s . y > 0``: ``y = A s`` for one SPD A
    (diagonal plus rank one, so d = 8229 costs nothing)."""
    diag, u = rng.random(d) + 0.5, rng.normal(size=d) / np.sqrt(d)
    S = rng.normal(size=(n, d))
    return S, S * diag + np.outer(S @ u, u)


def _bfgs_direction(g, S, Y):
    """``-H g`` by the BFGS recursion itself, pairs oldest first:
    ``H_i = V_i^T H_{i-1} V_i + rho_i s_i s_i^T``, ``V_i = I - rho_i y_i
    s_i^T``, ``H_0 = (s.y / y.y of the newest pair) I``; no pair: ``-g``.
    Applied to the vector, never formed: d = 8229 costs m vectors."""
    if not len(S):
        return -g
    gamma = (S[-1] @ Y[-1]) / (Y[-1] @ Y[-1])

    def apply(i, v):
        if i == 0:
            return gamma * v
        s, y = S[i - 1], Y[i - 1]
        rho = 1.0 / (y @ s)
        u = apply(i - 1, v - rho * y * (s @ v))
        return u - rho * s * (y @ u) + rho * s * (s @ v)

    return -apply(len(S), g)


def test_bfgs_direction_oracle_is_the_dense_recursion(rng):
    d = 10
    S, Y = _pairs(rng, 3, d)
    g = rng.normal(size=d)
    H = (S[-1] @ Y[-1]) / (Y[-1] @ Y[-1]) * np.eye(d)
    for s, y in zip(S, Y):
        rho = 1.0 / (y @ s)
        V = np.eye(d) - rho * np.outer(y, s)
        H = V.T @ H @ V + rho * np.outer(s, s)
    np.testing.assert_allclose(_bfgs_direction(g, S, Y), -H @ g, rtol=1e-12)


def _filled_history(S, Y, d):
    """The history after ``len(S)`` stores into ``M`` slots (circular):
    ``s`` under a true ``store``, ``y`` with none (always), and a refused
    pair (``store=False``) aimed at the next slot after each one."""
    from photon_ml_tpu.optimize.common import history_store, history_zeros

    s_hist = history_zeros(M, d, jnp.float64)
    y_hist = history_zeros(M, d, jnp.float64)
    rho = jnp.zeros((M,))
    for k, (s, y) in enumerate(zip(S, Y)):
        s_hist = history_store(s_hist, k % M, jnp.asarray(s), jnp.asarray(True))
        y_hist = history_store(y_hist, k % M, jnp.asarray(y))
        junk = jnp.full((d,), jnp.nan)
        s_hist = history_store(s_hist, (k + 1) % M, junk, jnp.asarray(False))
        rho = rho.at[k % M].set(1.0 / (s @ y))
    return s_hist, y_hist, rho


# d under a lane row, off the 128 lanes, off the 1,024-element tile with
# the stride not filled (d < 8 tiles) and filled (8,229 -> 9,216)
@pytest.mark.parametrize("d", [10, 200, 1300, 8229])
@pytest.mark.parametrize("k", [0, 1, 3, 4, 6, 9])  # empty, partial, wrapped
def test_two_loop_direction_is_the_bfgs_recursion(rng, d, k):
    from photon_ml_tpu.optimize.common import _history_stride
    from photon_ml_tpu.optimize.lbfgs import two_loop_direction

    S, Y = _pairs(rng, k, d)
    g = rng.normal(size=d)
    s_hist, y_hist, rho = _filled_history(S, Y, d)
    assert s_hist.shape == (M * _history_stride(d),)
    assert _history_stride(d) == (d if d < 8192 else 9216)
    p = jax.jit(two_loop_direction, static_argnums=5)(
        jnp.asarray(g), s_hist, y_hist, rho, jnp.asarray(k), M)
    want = _bfgs_direction(g, S[-M:], Y[-M:])
    np.testing.assert_allclose(np.asarray(p), want, rtol=1e-9, atol=1e-12)


def test_two_loop_direction_under_vmap(rng):
    """The random effects' use: entities on the batch axis, each with its
    own ``k``; a slot of ``d`` in the tens takes ``d`` elements, not a tile."""
    from photon_ml_tpu.optimize.lbfgs import two_loop_direction

    d, ks = 7, [0, 2, 4, 7]
    problems = [_pairs(rng, k, d) for k in ks]
    G = rng.normal(size=(len(ks), d))
    hists = [_filled_history(S, Y, d) for S, Y in problems]
    s_hist, y_hist, rho = (jnp.stack(x) for x in zip(*hists))
    assert s_hist.shape == (len(ks), M * d)
    P = jax.vmap(lambda g, s, y, r, k: two_loop_direction(g, s, y, r, k, M))(
        jnp.asarray(G), s_hist, y_hist, rho, jnp.asarray(ks))
    for e, (S, Y) in enumerate(problems):
        np.testing.assert_allclose(
            np.asarray(P[e]), _bfgs_direction(G[e], S[-M:], Y[-M:]),
            rtol=1e-9, atol=1e-12)


def _margin_fit(X, y, l2, cfg):
    """``lbfgs_margin`` on a dense logistic problem, the callables written
    out (``parallel/data_parallel.py`` builds them from an objective)."""
    from photon_ml_tpu.optimize.lbfgs_margin import lbfgs_margin

    X, y = jnp.asarray(X), jnp.asarray(y)

    def loss_and_dir(m, mp):
        return (jnp.sum(jnp.logaddexp(0.0, m) - y * m),
                jnp.sum((jax.nn.sigmoid(m) - y) * mp))

    d = X.shape[1]
    return lbfgs_margin(
        lambda p: X @ p, loss_and_dir,
        lambda m: X.T @ (jax.nn.sigmoid(m) - y), lambda w: w,
        jnp.zeros(d), jnp.zeros(X.shape[0]), l2, cfg)


def history_parity_fit(name, d):
    """The fits whose results ``tests/data/history_parity_pr35.npz`` holds
    as the parent commit (PR 34: the history an ``[m, d]`` array) returned
    them: the file's logistic fixture at the widths of the cases above, and
    the vmapped fit of the random effects."""
    fg, obj, batch, X, y, _, l2 = _logreg_problem(
        np.random.default_rng(35), n=60, d=d)
    cfg = OptimizerConfig(max_iters=25, tolerance=1e-9, history=5)
    w0 = jnp.zeros(d)
    if name == "lbfgs":
        return lbfgs(fg, w0, cfg)
    if name == "owlqn":
        return owlqn(fg, w0, 0.3, cfg)
    if name == "lbfgs_margin":
        return _margin_fit(X, y, l2, cfg)
    assert name == "lbfgs_vmap"
    rng = np.random.default_rng(36)
    Xe = jnp.asarray(rng.normal(size=(6, 40, d)))
    ye = jnp.asarray((rng.random((6, 40)) < 0.5).astype(float))

    def one(Xi, yi):
        def fg_i(w):
            m = Xi @ w
            return (jnp.sum(jnp.logaddexp(0.0, m) - yi * m) + 0.5 * w @ w,
                    Xi.T @ (jax.nn.sigmoid(m) - yi) + w)
        return lbfgs(fg_i, w0, cfg)

    return jax.vmap(one)(Xe, ye)


HISTORY_PARITY_CASES = [(name, d) for name in ("lbfgs", "lbfgs_margin",
                                               "owlqn")
                        for d in (10, 200, 8229)] + [("lbfgs_vmap", 10)]
HISTORY_PARITY_FIELDS = ("w", "value", "grad_norm", "iterations",
                         "converged", "loss_history", "grad_norm_history")


@pytest.mark.parametrize("name,d", HISTORY_PARITY_CASES)
def test_history_layout_keeps_results_bit_equal_to_parent(name, d):
    res = history_parity_fit(name, d)
    assert np.all(np.asarray(res.iterations) > 5)
    with np.load(HISTORY_DATA) as parent:
        for field in HISTORY_PARITY_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field)),
                parent[f"{name}-{d}/{field}"], err_msg=field)
