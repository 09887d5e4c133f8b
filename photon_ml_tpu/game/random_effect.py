"""Per-entity random-effect training and scoring.

Equivalent of the reference's ``RandomEffectCoordinate.trainModel`` /
``RandomEffectOptimizationProblem`` (SURVEY.md §4.3; reference mount empty):
the reference runs ``mapValues`` of local Breeze solves over an entity-keyed
RDD — thousands of small independent optimizations, executor-local. Here
each size bucket solves ALL its entities at once with ``vmap`` of the jitted
optimizer (one XLA program per bucket shape), optionally sharded over a mesh
``entity`` axis with ``shard_map`` — embarrassingly parallel, no collectives,
exactly like the reference's no-comm local solves.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu.analysis.sanitizers import nan_guard_check
from photon_ml_tpu.game.data import RandomEffectTrainData, REScoreBucket
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.obs.metrics import training_metrics
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optimize import OptimizerConfig, get_optimizer
from photon_ml_tpu.types import LabeledBatch, SparseFeatures


# f32 contractions in full precision: the TPU's default rounds the
# operands of an f32 matmul to bfloat16, which a Newton step on D <= 128
# columns cannot afford and does not need to (the MXU work is tiny)
_EXACT = jax.lax.Precision.HIGHEST


# The TPU's vector width. An entity axis on the lanes is padded to it in
# device memory whatever its length.
_LANES = 128


def _spd_solve(H, rhs):
    """``H^-1 rhs`` for a batch of small symmetric positive definite
    matrices: ``H [E, D, D]``, ``rhs [E, D]`` or ``[E, D, R]``.

    Elimination without pivoting (SPD needs none) with the entities on the
    minor axis, ``[D, D + R, E]``: every step of the two ``D``-step loops
    is element-wise over 128 entities a vector instruction, where a batched
    LU works on one ``D``-wide matrix at a time and pads each to the tile.
    The forward loop carries the right-hand sides as ``R`` more columns;
    the back substitution carries them alone. Ahead of the LU at both ends
    of what ``"auto"`` hands it (v5e, PERF.md section 6, PR 33: 8,858 x 21
    in 1.7 ms against 52, 4,096 x 128 in 121 against 253), so one path.

    Nothing is summed across entities or along ``D``, and the entity axis
    is filled up to whole vectors (with copies of the last entity), so
    every entity goes through the same instructions and its result does
    not depend on how many share the call. Unfilled, XLA's CPU code for 3
    or 7 entities contracts other multiply-adds than for 300.

    A pivot that is not positive (``H`` singular or indefinite to working
    precision) makes that entity's result NaN and no other's: the caller
    masks a non-finite step.
    """
    E, D = H.shape[:2]
    vector = rhs.ndim == 2
    aug = jnp.concatenate([H, rhs[:, :, None] if vector else rhs], axis=2)
    aug = jnp.pad(aug, ((0, -E % _LANES), (0, 0), (0, 0)), mode="edge")
    aug = jnp.transpose(aug, (1, 2, 0))
    row_id = jnp.arange(D)[:, None, None]

    def column(A, k):
        """Column ``k`` as ``[D, 1, E]`` and its pivot, NaN unless positive."""
        c = jax.lax.dynamic_slice_in_dim(A, k, 1, axis=1)
        p = jax.lax.dynamic_index_in_dim(c, k, axis=0)
        return c, jnp.where(p > 0, p, jnp.nan)

    def eliminate(k, A):
        c, p = column(A, k)
        l = jnp.where(row_id > k, c / p, 0.0)
        return A - l * jax.lax.dynamic_index_in_dim(A, k, axis=0)

    aug = jax.lax.fori_loop(0, D, eliminate, aug)
    U = aug[:, :D]  # upper triangle; what is left below it is never read

    def substitute(j, Y):
        k = D - 1 - j
        c, p = column(U, k)
        x_k = jax.lax.dynamic_index_in_dim(Y, k, axis=0) / p
        return jnp.where(row_id < k, Y - c * x_k,
                         jnp.where(row_id == k, x_k, Y))

    X = jnp.transpose(jax.lax.fori_loop(0, D, substitute, aug[:, D:]),
                      (2, 0, 1))[:E]
    return X[:, :, 0] if vector else X


def _newton_dense_solver(local_dim: int, task: str,
                         config: OptimizerConfig,
                         compute_variance: bool | str, norm_mode: int = 0):
    """Batched dense Newton (IRLS) bucket solver — the TPU-first RE path.

    Per-entity dims are small (subspace-projected, typically ≤ 64), so the
    whole bucket solves as BATCHED DENSE linear algebra instead of a
    ``vmap`` of sparse L-BFGS loops: rows densify once to ``X [E, D, N]``
    (a k-step scan, no scatter), every Newton iteration is two einsums
    (gradient ``X^T d1``, Hessian ``X^T diag(d2) X`` — MXU contractions)
    plus one batched SPD solve (``_spd_solve``), and a 4-level step-halving
    safeguard keeps descent monotone. The returned ``solve`` carries its
    two halves, ``solve.densify`` and ``solve.solve_dense``, so a caller
    that keeps ``X`` between sweeps runs the second alone. A vmapped
    L-BFGS executes all
    entities' line searches in lockstep on the VPU; this formulation puts
    the FLOPs where the TPU wants them (same trade the reference's local
    Breeze Newton solvers make per executor, batched instead of mapped).

    Same signature/returns as the vmapped solver: (W, variances,
    converged, iterations) per entity. L1 is not supported (the caller
    auto-routes l1 > 0 to OWL-QN).
    """
    D = local_dim
    loss = get_loss(task)
    tol = config.tolerance
    max_iters = config.max_iters
    # match_vma: under the entity-axis shard_map the data varies over
    # the mesh axis but fresh zeros/True carries do not; align every
    # loop carry or scan/while_loop reject the carry types (no-op
    # outside shard_map)
    from photon_ml_tpu.optimize.common import match_vma, match_vma_tree

    @jax.named_scope("photon.re/densify")
    def densify(indices_t, values_t, f_loc, s_loc):
        """Slot-major sparse rows ``[E, k, N]`` -> ``X [E, D, N]``: rows
        along the minor axis, where the TPU keeps 128 lanes (a minor axis
        of ``k`` or ``D`` entries would be padded to 128 in HBM)."""
        E, kk, N = indices_t.shape
        # X[e, idx[e, j, n], n] += val[e, j, n], as a k-step scan of
        # masked adds (no scatter — TPU scatter serializes). Padding
        # slots carry value 0 and add nothing wherever they point.
        iota = jnp.arange(D, dtype=indices_t.dtype)[None, :, None]

        def add_slot(X, j):
            idx_j = jnp.take(indices_t, j, axis=1)[:, None, :]  # [E, 1, N]
            val_j = jnp.take(values_t, j, axis=1)[:, None, :]
            return X + jnp.where(idx_j == iota, val_j, 0.0), None

        X, _ = jax.lax.scan(
            add_slot,
            match_vma(jnp.zeros((E, D, N), values_t.dtype), values_t),
            jnp.arange(kk))
        # normalization in data space: x' = (x - s) * f per local slot
        # (exactly the sparse path's effective-coefficient fold)
        if norm_mode == 2:
            X = (X - s_loc[:, :, None]) * f_loc[:, :, None]
        elif norm_mode == 1:
            X = X * f_loc[:, :, None]
        return X

    def solve_dense(X, labels, weights, offs, w0, l2):
        E = X.shape[0]
        dt = X.dtype
        live = weights != 0  # [E, N]; padding rows are inert

        def margins(W):
            m = jnp.einsum("edn,ed->en", X, W, precision=_EXACT) + offs
            return jnp.where(live, m, 0.0)  # mask BEFORE the loss

        def fval(W):
            per = loss.loss(margins(W), labels)
            data = jnp.sum(jnp.where(live, weights * per, 0.0), axis=1)
            return data + 0.5 * l2 * jnp.sum(W * W, axis=1)

        d1_fn = jax.grad(lambda m, y: jnp.sum(loss.loss(m, y)))

        @jax.named_scope("photon.re/newton/grad_hess")
        def grad_hess(W):
            m = margins(W)
            wd1 = jnp.where(live, weights * d1_fn(m, labels), 0.0)
            wd2 = jnp.where(live, weights * loss.d2(m, labels), 0.0)
            g = jnp.einsum("edn,en->ed", X, wd1, precision=_EXACT) + l2 * W
            H = (jnp.einsum("edn,en,efn->edf", X, wd2, X, precision=_EXACT)
                 + l2 * jnp.eye(D, dtype=dt))
            return g, H

        @jax.named_scope("photon.re/newton/solve")
        def newton_step(H, g):
            return _spd_solve(H, g)

        @jax.named_scope("photon.re/newton/halving")
        def halving(W, f, step):
            # per-entity step-halving: try alpha in {1, 1/2, 1/4, 1/8},
            # keep the largest that does not increase f (batched, static).
            # "Does not increase" to within the rounding of f itself: next
            # to the optimum a Newton step lowers f by less than one ulp of
            # it, and which way the sum's last bit falls is no verdict on
            # the step
            alphas = jnp.asarray([1.0, 0.5, 0.25, 0.125], dt)
            f_tries = jnp.stack(
                [fval(W - a * step) for a in alphas])  # [4, E]
            noise = 4 * jnp.finfo(dt).eps * jnp.maximum(jnp.abs(f), 1.0)
            ok = f_tries <= (f + noise)[None, :]
            first_ok = jnp.argmax(ok, axis=0)  # first True, else 0
            any_ok = jnp.any(ok, axis=0)
            a_sel = jnp.where(any_ok, alphas[first_ok], 0.0)  # 0 = stall
            f_new = jnp.where(any_ok,
                              jnp.take_along_axis(
                                  f_tries, first_ok[None, :], axis=0)[0],
                              f)
            return any_ok, a_sel, f_new

        f0 = fval(w0)
        g0, _ = grad_hess(w0)
        g0n = jnp.linalg.norm(g0, axis=1)
        # converged_check semantics, batched: an explicit tol <= 0 disables
        # the tests; a positive tol is clamped to a few ulps of the dtype
        eff_tol = jnp.where(tol > 0,
                            jnp.maximum(jnp.asarray(tol, dt),
                                        4 * jnp.finfo(dt).eps),
                            jnp.asarray(0.0, dt))

        def cond(state):
            return jnp.any(state[2])  # any entity still active

        def body(state):
            W, f, active, conv_seen, iters = state
            g, H = grad_hess(W)
            step = newton_step(H, g)
            any_ok, a_sel, f_new = halving(W, f, step)
            gnorm = jnp.linalg.norm(g, axis=1)
            # converged_check semantics, batched: |f_prev - f| <= tol *
            # max(|f_prev|, 1) OR gnorm <= tol * max(||g0||, 1). The
            # relative-loss half needs an accepted step (a rejected step's
            # zero delta would pass spuriously), but the gradient half
            # fires regardless: step-halving failing AT the optimum (fp
            # noise, singular-H NaN step) is convergence, not a stall —
            # same policy as the L-BFGS paths.
            delta = jnp.abs(f - f_new)
            conv = active & (eff_tol > 0) & (
                (any_ok & (delta <= eff_tol * jnp.maximum(jnp.abs(f), 1.0)))
                | (gnorm <= eff_tol * jnp.maximum(g0n, 1.0)))
            # a rejected step must be MASKED, not zero-multiplied: with a
            # singular H (rank-deficient entity, l2=0) the solve returns
            # NaN and 0 * NaN would poison W permanently. An entity that
            # converges on its FIRST iteration also keeps its incoming
            # point (conv & first): it was already at its stopping point,
            # and taking the probed sub-tolerance step would make a
            # warm-started re-solve of a converged entity drift by one
            # noise-level step every CD sweep — defeating active-set
            # freezing (a frozen entity must be a true no-op re-solve;
            # same policy as optimize/lbfgs.py).
            first = iters == 0
            keep = conv & first
            W_new = jnp.where((active & any_ok & ~keep)[:, None],
                              W - a_sel[:, None] * step, W)
            iters_new = iters + active.astype(iters.dtype)
            active_new = active & ~conv & any_ok & (iters_new < max_iters)
            f_out = jnp.where(active & ~keep, f_new, f)
            return (W_new, f_out, active_new, conv_seen | conv, iters_new)

        state = match_vma_tree(
            (jnp.asarray(w0, dt), f0, jnp.ones((E,), bool),
             jnp.zeros((E,), bool), jnp.zeros((E,), jnp.int32)), X)
        W, f, active, conv_seen, iters = jax.lax.while_loop(cond, body,
                                                            state)
        converged = conv_seen
        if compute_variance:
            if compute_variance == "full":
                _, H_fin = grad_hess(W)
                Hinv = _spd_solve(
                    H_fin, jnp.broadcast_to(jnp.eye(D, dtype=dt),
                                            (E, D, D)))
                var = jnp.diagonal(Hinv, axis1=1, axis2=2)
            else:
                diag = jnp.einsum("edn,en,edn->ed", X,
                                  jnp.where(live, weights
                                            * loss.d2(margins(W), labels),
                                            0.0), X, precision=_EXACT) + l2
                var = 1.0 / jnp.maximum(diag, jnp.finfo(dt).tiny)
        else:
            var = jnp.zeros((E, 0), dt)
        return W, var, converged, iters

    def solve(indices, values, labels, weights, offs, w0, f_loc, s_loc,
              l2, l1):
        del l1  # caller guarantees 0 (owlqn route)
        X = densify(jnp.swapaxes(indices, 1, 2), jnp.swapaxes(values, 1, 2),
                    f_loc, s_loc)
        return solve_dense(X, labels, weights, offs, w0, l2)

    solve.densify = densify
    solve.solve_dense = solve_dense
    return solve


def _solver_for_bucket(local_dim: int, task: str, optimizer: str,
                       config: OptimizerConfig, compute_variance: bool | str,
                       norm_mode: int = 0):
    """Build the vmapped per-bucket solve function.

    ``norm_mode``: 0 = no normalization; 1 = per-entity scale factors;
    2 = factors + shifts. Each entity carries its own local factor/shift
    vectors (the global context gathered through its subspace projection,
    with the intercept slot pre-pinned to 1/0, so ``intercept_index=-1``).

    ``optimizer="newton"`` selects the batched dense-Newton solver
    (``_newton_dense_solver``) instead of a vmap of sparse optimizers."""
    if optimizer == "newton":
        return _newton_dense_solver(local_dim, task, config,
                                    compute_variance, norm_mode)
    opt = get_optimizer(optimizer)

    def solve_one(indices, values, labels, weights, offs, w0, f_loc, s_loc,
                  l2, l1):
        ctx = None
        if norm_mode == 1:
            ctx = NormalizationContext(f_loc, None, -1)
        elif norm_mode == 2:
            ctx = NormalizationContext(f_loc, s_loc, -1)
        obj = make_objective(task, normalization=ctx)
        batch = LabeledBatch(
            SparseFeatures(indices, values, dim=local_dim), labels, offs, weights
        )
        fg = lambda w: obj.value_and_grad(w, batch, l2)
        if optimizer == "owlqn":
            res = opt(fg, w0, l1, config)
        else:
            res = opt(fg, w0, config)
        # compute_variance: False | True/"diagonal" | "full" — the FULL
        # (d x d inverse) mode is feasible per entity because local dims
        # are small; vmap batches the tiny solves.
        if compute_variance:
            mode = "full" if compute_variance == "full" else "diagonal"
            var = obj.coefficient_variances(res.w, batch, l2, mode=mode)
        else:
            var = jnp.zeros((0,), res.w.dtype)
        return res.w, var, res.converged, res.iterations

    return jax.vmap(solve_one, in_axes=(0,) * 8 + (None, None))


# Every jitted bucket solver ever built (the cached builders below append
# theirs exactly once per cache key). ``re_solver_compile_count`` sums their
# per-shape executable counts — the bench/test invariant that the active-set
# path's power-of-two sub-bucket ladder stops compiling once warmed.
_SOLVER_REGISTRY: List = []


def re_solver_compile_count() -> int:
    """Total compiled executables across all random-effect bucket solvers
    (every distinct entity-block shape is one executable)."""
    total = 0
    for fn in _SOLVER_REGISTRY:
        size = getattr(fn, "_cache_size", None)
        if callable(size):
            total += int(size())
    return total


def _named(fn, name: str):
    """``fn`` under a ``photon_*`` program name (the trace's ``XLA
    Modules`` line shows ``jit_<name>``)."""
    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


@functools.lru_cache(maxsize=256)
def _jitted_placed_solver(local_dim, task, optimizer, config,
                          compute_variance, norm_mode=0):
    """The jitted per-bucket solver over a placed bucket's slot-major
    tables ``[E, k, N]`` (cached, so repeated coordinate-descent steps with
    identical shapes reuse one XLA compilation): row-major inside the
    program, where the compiler is free to fuse the transpose away."""
    solver = _solver_for_bucket(local_dim, task, optimizer, config,
                                compute_variance, norm_mode)

    def placed(indices_t, values_t, *rest):
        return solver(jnp.swapaxes(indices_t, 1, 2),
                      jnp.swapaxes(values_t, 1, 2), *rest)

    fn = jax.jit(_named(placed, f"photon_re_solve_{optimizer}"))
    _SOLVER_REGISTRY.append(fn)
    return fn


@functools.lru_cache(maxsize=256)
def _jitted_newton_halves(local_dim, task, config, compute_variance,
                          norm_mode=0):
    """The dense-Newton solver as two programs, for a caller that keeps
    ``X``: (densify, solve_dense)."""
    solver = _newton_dense_solver(local_dim, task, config, compute_variance,
                                  norm_mode)
    densify = jax.jit(_named(solver.densify, "photon_re_densify"))
    solve = jax.jit(_named(solver.solve_dense, "photon_re_solve_newton"))
    _SOLVER_REGISTRY.extend((densify, solve))
    return densify, solve


@functools.lru_cache(maxsize=256)
def _jitted_sharded_solver(local_dim, task, optimizer, config, compute_variance,
                           mesh, axis, norm_mode=0):
    solver = _solver_for_bucket(local_dim, task, optimizer, config,
                                compute_variance, norm_mode)
    spec = (P(axis),) * 8 + (P(), P())
    # check_vma=False: the batched solver is per-entity independent — no
    # collective, nothing relies on vma-driven transposes
    sharded = shard_map(
        solver, mesh=mesh, in_specs=spec,
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
        check_vma=False,
    )
    fn = jax.jit(_named(sharded, f"photon_re_solve_{optimizer}_sharded"))
    _SOLVER_REGISTRY.append(fn)
    return fn


def _local_normalization(buckets, norm: NormalizationContext):
    """Gather the global normalization context into per-entity local
    vectors: for each bucket, (f_loc [E,D], s_loc [E,D] | None,
    intercept_pos [E] | None). Padding slots (projection -1) get f=1, s=0;
    the global intercept slot is pinned (f=1, s=0) so the local context
    runs with ``intercept_index=-1`` and the fold-back is explicit."""
    f_g = None if norm.factors is None else np.asarray(norm.factors).copy()
    s_g = None if norm.shifts is None else np.asarray(norm.shifts).copy()
    ii = norm.intercept_index
    if f_g is not None and ii >= 0:
        f_g[ii] = 1.0
    if s_g is not None and ii >= 0:
        s_g[ii] = 0.0
    out = []
    for bucket in buckets:
        from photon_ml_tpu.game.data import SketchProjection

        if any(isinstance(lm, SketchProjection) for lm in bucket.local_maps):
            raise ValueError(
                "normalization is not supported with projection='random' "
                "(count-sketch slots mix features); use projection='subspace'")
        proj = np.asarray(bucket.projection)
        safe = np.maximum(proj, 0)
        f_loc = (np.where(proj >= 0, f_g[safe], 1.0) if f_g is not None
                 else np.ones_like(proj, np.float64))
        s_loc = None
        pos = None
        if s_g is not None:
            s_loc = np.where(proj >= 0, s_g[safe], 0.0)
            has = proj == ii
            if ii < 0 or not has.any(axis=1).all():
                raise ValueError(
                    "shift normalization requires the intercept feature in "
                    "every entity's feature subspace")
            pos = has.argmax(axis=1)
        out.append((f_loc, s_loc, pos))
    return out


def _re_to_training_space(W_raw, f_loc, s_loc, pos):
    """Per-entity inverse of the model-space fold (warm starts)."""
    W = jnp.asarray(W_raw, f_loc.dtype)
    if s_loc is not None:
        rows = jnp.arange(W.shape[0])
        w_noint = W.at[rows, pos].set(0.0)
        W = W.at[rows, pos].add(jnp.sum(s_loc * w_noint, axis=1))
    return W / f_loc


def _re_to_model_space(W_opt, f_loc, s_loc, pos):
    """Optimizer-space bucket coefficients -> raw-feature space."""
    W = W_opt * f_loc
    if s_loc is not None:
        adjust = -jnp.sum(s_loc * W, axis=1)  # s_loc is 0 at the intercept
        W = W.at[jnp.arange(W.shape[0]), pos].add(adjust)
    return W


# Per-platform random-effect solver default for ``optimizer="auto"``
# (VERDICT r3 #7). Measured by scripts/bench_game.py: on CPU the vmapped
# sparse L-BFGS wins (28.4k entities/s vs 16.6k for the batched dense
# Newton at E=2000, rows/entity=32, d_local=16). On the TPU the batched
# dense-Newton IRLS wins: per entity it is [E, d, d] einsum Hessians
# (systolic-array work) + an elimination with the entities on the lanes
# (``_spd_solve``), where the vmapped L-BFGS path is gather/VPU-bound. An
# unmeasured platform logs one line when its default is used, so no silent
# cross-platform fallback remains (VERDICT r4 missing #3).
_RE_SOLVER_DEFAULT = {"cpu": "lbfgs", "tpu": "newton"}
# tpu: newton 7919 entities/s vs lbfgs 2315 at E=100k, rows=64,
# d_local=32 (builder-measured on a v5e, 2026-07-31, not re-measured
# since).
_RE_SOLVER_MEASURED = {"cpu", "tpu"}
_warned_unmeasured = set()

# What one solver execution may hold on the device, in bytes: the block's
# rows, their dense form and the solver's intermediates. 100k entities in
# one program exhausted v5e HBM and hard-crashed the TPU worker; a block of
# 1 GiB leaves the resident tables and the fixed effect their room, and is
# large enough that every bucket of a MovieLens-shaped effect is one block.
_RE_BLOCK_BYTES = 1 << 30
# The dense ``X`` of an effect's buckets, which no sweep changes, stays on
# the device up to this many bytes an effect; past it a bucket densifies
# again in every solve.
_RE_DENSE_KEEP_BYTES = 2 << 30


def _tiled(minor: int, second: int) -> int:
    """Elements a ``[second, minor]`` slab takes in device memory: the
    TPU pads the minor axis to 128 lanes and the next to 8 sublanes."""
    return (-(-max(minor, 1) // 128) * 128) * (-(-max(second, 1) // 8) * 8)


def entity_bytes(N: int, k: int, D: int, itemsize: int, optimizer: str,
                 history: int = 5) -> int:
    """Device bytes one entity of a ``[N, k]``-row, ``D``-wide bucket
    takes inside a solver execution, tiles counted: its sparse rows
    (slot-major, ``[k, N]``) and six row vectors and, for the dense
    Newton, ``X [D, N]`` with one temporary of its size, the four trial
    points' margins, and four ``[D, D]`` tiles: the Hessian, the form it
    is joined to the gradient in, and the solve's two buffers with the
    entities on the lanes (``D^2`` elements each, at most a tile); for a
    vmapped optimizer, a row-major copy of the rows and its history."""
    rows = _tiled(N, k) * (4 + itemsize) + 6 * _tiled(N, 1) * itemsize // 8
    if optimizer == "newton":
        return rows + itemsize * (2 * _tiled(N, D) + 4 * _tiled(N, 1) // 8
                                  + 4 * _tiled(D, D) + 8 * D)
    return rows + itemsize * (2 * N * max(k, 128) + (2 * history + 8) * D)


def block_entities(n_entities: int, per_entity: int, n_dev: int = 1,
                   budget: Optional[int] = None) -> int:
    """Entities a solver execution takes: as many as ``budget`` bytes
    hold (at least one), no more than there are, rounded up to the
    device count of an entity mesh."""
    budget = _RE_BLOCK_BYTES if budget is None else budget
    bs = max(1, min(int(budget) // max(int(per_entity), 1), n_entities))
    return -(-bs // n_dev) * n_dev


def upload(a, dtype=None) -> jax.Array:
    """Host array -> device, counted (``photon_train_h2d_bytes_total``).
    A device array passes through, cast if asked."""
    if isinstance(a, jax.Array):
        return a if dtype is None or a.dtype == dtype else a.astype(dtype)
    a = np.asarray(a) if dtype is None else np.asarray(a, dtype)
    training_metrics().count_h2d(a.nbytes)
    return jnp.asarray(a)


def fetch(a, what: str) -> np.ndarray:
    """Device array -> host numpy: the GAME path's one sync point. The
    call waits for the value, inside a ``cd.fetch`` span (arg ``what``:
    which value, e.g. ``train_loss``); a device array counts its bytes
    (``photon_train_d2h_bytes_total``), one sync and the seconds the host
    waited (``photon_train_syncs_total``, ``_sync_wait_seconds_total``)."""
    if not isinstance(a, jax.Array):
        return np.asarray(a)
    with obs_trace.span("cd.fetch", cat="train", what=what):
        t0 = time.perf_counter()
        out = np.asarray(a)
        waited = time.perf_counter() - t0
    tm = training_metrics()
    tm.count_d2h(out.nbytes)
    tm.count_sync(waited)
    return out


@dataclasses.dataclass
class PlacedBucket:
    """One bucket's training arrays in device memory, in the working
    dtype: what ``REBucket`` holds on the host, plus the dense ``X`` per
    normalization once a Newton solve has built it."""

    indices: jax.Array  # int32 [E, k, N]: slot-major, rows on the lanes
    values: jax.Array  # [E, k, N]
    labels: jax.Array  # [E, N]
    weights: jax.Array  # [E, N]
    sample_idx: jax.Array  # int32 [E, N], -1 pad
    dense: dict = dataclasses.field(default_factory=dict)

    def arrays(self):
        return (self.indices, self.values, self.labels, self.weights,
                self.sample_idx)


@dataclasses.dataclass
class PlacedRandomEffect:
    """A ``RandomEffectTrainData`` placed on the device once, for every
    sweep of every run over it (``CoordinateDescent`` keeps it in its
    ``dataset_cache``)."""

    buckets: List[PlacedBucket]
    dtype: object
    nbytes: int
    dense_bytes: int = 0
    # id(normalization) -> (normalization, per-bucket (f_loc, s_loc, pos))
    norms: dict = dataclasses.field(default_factory=dict)

    def local_norm(self, data: RandomEffectTrainData,
                   normalization: NormalizationContext):
        entry = self.norms.get(id(normalization))
        if entry is None or entry[0] is not normalization:
            placed = [
                (upload(f, self.dtype),
                 None if s is None else upload(s, self.dtype),
                 None if pos is None else upload(pos))
                for f, s, pos in _local_normalization(data.buckets,
                                                      normalization)]
            entry = self.norms[id(normalization)] = (normalization, placed)
        return entry[1]


def _slot_major(a: np.ndarray) -> np.ndarray:
    """Host ``[E, N, k]`` -> ``[E, k, N]``, contiguous."""
    return np.ascontiguousarray(np.swapaxes(np.asarray(a), 1, 2))


def place_random_effect(data: RandomEffectTrainData,
                        dtype=jnp.float32) -> PlacedRandomEffect:
    """Upload every bucket's training arrays (span ``re.place``)."""
    dtype = jnp.dtype(dtype)
    with obs_trace.span("re.place", cat="train", effect=data.effect_name,
                        what="train", buckets=len(data.buckets)) as sp:
        buckets = [
            PlacedBucket(upload(_slot_major(b.indices), np.int32),
                         upload(_slot_major(b.values), dtype),
                         upload(b.labels, dtype), upload(b.weights, dtype),
                         upload(b.sample_idx, np.int32))
            for b in data.buckets]
        nbytes = sum(a.nbytes for b in buckets for a in b.arrays())
        sp.set(bytes=nbytes)
    return PlacedRandomEffect(buckets, dtype, nbytes)


@dataclasses.dataclass
class PlacedScoreView:
    """A score view in device memory: per bucket (indices ``[E, k, M]``,
    values ``[E, k, M]``, target ``[E, M]``), slot-major as the training
    tables are, ``target`` the row each slot scores, padding pointed at
    the spare slot ``num_samples``."""

    buckets: List[tuple]
    num_samples: int
    dtype: object
    nbytes: int


def place_score_view(score_view: Sequence[REScoreBucket], num_samples: int,
                     dtype=jnp.float32) -> PlacedScoreView:
    dtype = jnp.dtype(dtype)
    with obs_trace.span("re.place", cat="train", what="score_view",
                        buckets=len(score_view)) as sp:
        buckets = []
        for view in score_view:
            sidx = np.asarray(view.sample_idx)
            target = np.where(sidx >= 0, sidx, num_samples).astype(np.int32)
            buckets.append((upload(_slot_major(view.indices), np.int32),
                            upload(_slot_major(view.values), dtype),
                            upload(target)))
        nbytes = sum(a.nbytes for b in buckets for a in b)
        sp.set(bytes=nbytes)
    return PlacedScoreView(buckets, int(num_samples), dtype, nbytes)


def _pad_entities(a: jax.Array, width: int) -> jax.Array:
    """Zero-pad axis 0 to ``width`` (padded entities have weight-0 rows:
    their objective is constant and the solver converges immediately)."""
    pad = width - a.shape[0]
    if pad == 0:
        return a
    return jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])


def _active_width(n_active: int, block: int, n_dev: int) -> int:
    """Padded width for an active-set sub-bucket: the next power of two
    (rounded up to the device count), capped at the full block width. The
    power-of-two ladder bounds the number of distinct solver shapes at
    log2(block) — after the first couple of shrinking sweeps every width
    has been compiled and the compile counter stays flat."""
    w = 1 << max(n_active - 1, 0).bit_length()
    # floor the ladder at 32: solving 9 vs 32 entities costs the same under
    # vmap, and every distinct width below the floor would be one more XLA
    # compile for no win
    w = -(-max(w, 32) // n_dev) * n_dev
    return min(w, block)


# "auto" only picks the dense-Newton solver up to this per-entity dim:
# its [block, d, d] Hessians are d^2 x 4 B an entity (8 GB at the d=351 CD
# bucket that crashed the compile of the batched LU this path called then
# — builder-measured on a v5e, 2026-07-31, not re-measured since) and the
# solve's work grows as d^3; the vmapped L-BFGS memory is O(d) per entity
# and handles wide subspaces fine.
_RE_NEWTON_MAX_DIM = 128


def resolve_re_optimizer(optimizer: str, local_dim: int = None) -> str:
    """Resolve ``"auto"`` to the per-platform default solver (measured
    where a measurement exists; design-predicted and logged otherwise).
    ``local_dim`` (the bucket's per-entity dimension, when known) gates
    the dense-Newton choice — see ``_RE_NEWTON_MAX_DIM``."""
    if optimizer != "auto":
        return optimizer
    platform = jax.devices()[0].platform
    choice = _RE_SOLVER_DEFAULT.get(platform, "lbfgs")
    if (choice == "newton" and local_dim is not None
            and local_dim > _RE_NEWTON_MAX_DIM):
        choice = "lbfgs"
    if platform not in _RE_SOLVER_MEASURED and platform not in _warned_unmeasured:
        _warned_unmeasured.add(platform)
        import logging

        logging.getLogger("photon_ml_tpu").info(
            "optimizer='auto' on platform %r -> %r (design-predicted "
            "default, no hardware measurement yet; run "
            "scripts/bench_game.py on this platform to measure)",
            platform, choice)
    return choice


@functools.partial(jax.jit, static_argnames=("width",))
def _take_block(arrays, start, width: int):
    return tuple(jax.lax.dynamic_slice_in_dim(a, start, width, axis=0)
                 for a in arrays)


@jax.jit
def _take_entities(arrays, sel):
    """Rows ``sel`` of every array; an index past the end gives a padding
    entity (zeros: weight-0 rows, and sample index 0 under weight 0)."""
    return tuple(jnp.take(a, sel, axis=0, mode="fill", fill_value=0)
                 for a in arrays)


@jax.jit
@jax.named_scope("photon.cd/residual")
def _gather_offsets(offsets, sample_idx):
    # padding rows (sample_idx == -1) carry weight 0, offset value irrelevant
    return jnp.where(sample_idx >= 0,
                     jnp.take(offsets, jnp.maximum(sample_idx, 0), axis=0),
                     0.0)


def _run_entity_blocks(run, per_entity, shared, n_entities: int, bs: int):
    """Drive a bucket solver over entity blocks of ``bs`` and put the
    per-entity results together, all on the device. ``per_entity`` are the
    arrays with a leading entity axis, ``shared`` the scalars every block
    gets. One block: the arrays as they are (padded where ``bs`` exceeds
    them). More: every block is a ``bs``-wide window, the last one moved
    back to end with the bucket — the entities it shares with the block
    before are solved twice, to the same result — so one compiled shape
    serves all and nothing is padded."""
    if bs >= n_entities:
        blk = tuple(_pad_entities(a, bs) for a in per_entity)
        return tuple(o[:n_entities] for o in run(*blk, *shared)), 1
    parts, starts = [], list(range(0, n_entities, bs))
    for s in starts:
        s0 = min(s, n_entities - bs)
        out = run(*_take_block(per_entity, np.int32(s0), width=bs), *shared)
        parts.append(tuple(o[s - s0:] for o in out))
    return tuple(jnp.concatenate(p) for p in zip(*parts)), len(starts)


@dataclasses.dataclass(frozen=True)
class RandomEffectFitResult:
    """Per-entity arrays stay on the device; the three summary numbers
    are fetched when read."""

    coefficients: List[jax.Array]  # per bucket [E, D]
    variances: Optional[List[jax.Array]]
    # per-entity detail (one array per bucket): the active-set CD loop uses
    # these to decide which entities to freeze between sweeps. Entities not
    # re-solved this call (active-set frozen) report converged=True and
    # iterations=0 — their objective was not touched.
    converged: List[jax.Array]  # bool [E] per bucket
    iterations: List[jax.Array]  # int32 [E] per bucket
    entities: int = 0
    entities_solved: int = 0
    blocks: int = 0

    def counts(self) -> dict:
        """``converged`` (entities), ``iterations_sum``, ``iterations_max``
        over every bucket: one fetch, which waits for the solves."""
        if not any(c.shape[0] for c in self.converged):
            return {"converged": 0, "iterations_sum": 0, "iterations_max": 0}
        got = fetch(_fit_counts(tuple(self.converged),
                                tuple(self.iterations)), what="re.counts")
        return {"converged": int(got[0]), "iterations_sum": int(got[1]),
                "iterations_max": int(got[2])}

    @property
    def converged_fraction(self) -> float:
        return self.counts()["converged"] / max(self.entities, 1)

    @property
    def mean_iterations(self) -> float:
        """Over the entities actually solved this call."""
        return (self.counts()["iterations_sum"]
                / max(self.entities_solved, 1))


@jax.jit
def _fit_counts(converged, iterations):
    conv = sum(jnp.sum(c.astype(jnp.int32)) for c in converged)
    its = [i.astype(jnp.int32) for i in iterations if i.shape[0]]
    return jnp.stack([conv, sum(jnp.sum(i) for i in its),
                      jnp.max(jnp.stack([jnp.max(i) for i in its]))])


def train_random_effect(
    data: RandomEffectTrainData,
    offsets: jax.Array,
    task: str = "logistic",
    l2=0.0,
    l1=0.0,
    optimizer: str = "lbfgs",
    config: OptimizerConfig = OptimizerConfig(max_iters=50, history=5),
    w0: Optional[List[np.ndarray]] = None,
    mesh: Optional[Mesh] = None,
    axis: str = "entity",
    compute_variance: bool | str = False,  # False | "diagonal" | "full"
    dtype=jnp.float32,
    normalization: Optional[NormalizationContext] = None,
    active: Optional[Sequence[Optional[np.ndarray]]] = None,
    prev_variances: Optional[List[Optional[np.ndarray]]] = None,
    placed: Optional[PlacedRandomEffect] = None,
) -> RandomEffectFitResult:
    """Solve every entity's local GLM. ``offsets`` is the full-dataset
    residual-offset vector [n] from the coordinate-descent loop. L1 weight
    requires (and auto-routes to) the OWL-QN optimizer.

    ``placed`` is ``data`` in device memory (``place_random_effect``):
    with it the call uploads nothing but what is new (``offsets`` and
    ``w0`` where they are host arrays, the scalars); without it the
    tables are placed for this call alone. Coefficients, variances and
    the per-entity flags come back as device arrays.

    ``normalization`` (the shard's global context) is applied inside each
    per-entity objective via gathered local factor/shift vectors; incoming
    ``w0`` and returned coefficients stay in raw feature space (conversion
    happens here), so scoring/saving/warm-start paths are unchanged.

    ``active`` (the active-set CD path): one boolean mask [E] per bucket —
    only masked entities are re-solved. Their rows are gathered on the
    device into a power-of-two-padded sub-bucket (``_active_width``),
    solved with the same shape-bucketed jitted solver, and scattered back;
    frozen entities carry their ``w0`` coefficients (and ``prev_variances``)
    untouched and report converged=True / iterations=0. Requires ``w0``.
    A ``None`` mask entry means "solve the whole bucket"."""
    if np.asarray(l1).item() > 0 and optimizer != "owlqn":
        optimizer = "owlqn"
    if active is not None and w0 is None:
        raise ValueError("active-set training needs w0 (frozen entities "
                         "carry their previous coefficients)")
    dtype = jnp.dtype(dtype)
    if placed is None or placed.dtype != dtype:
        placed = place_random_effect(data, dtype)
    # "auto" stays unresolved here: the per-bucket local_dim feeds the
    # dense-Newton dimension gate inside the loop
    offsets = upload(offsets, dtype)
    local_norm = (None if normalization is None
                  else placed.local_norm(data, normalization))
    norm_mode = 0
    if normalization is not None:
        norm_mode = 2 if normalization.shifts is not None else 1
    l2_dev, l1_dev = upload(l2, dtype), upload(l1, dtype)
    itemsize = dtype.itemsize
    coeffs, variances = [], []
    conv_list, iter_list = [], []
    total, solved_total, blocks_total = 0, 0, 0
    for b, (bucket, dev) in enumerate(zip(data.buckets, placed.buckets)):
        E, D = bucket.num_entities, bucket.local_dim
        N, k = bucket.indices.shape[1:]
        w0_b = None if w0 is None else upload(w0[b], dtype)
        if E == 0:
            # degenerate bucket (no entities): nothing to solve — emit the
            # empty [0, D] shapes downstream consumers expect (scoring,
            # model building, warm start)
            coeffs.append(jnp.zeros((0, D), dtype))
            variances.append(jnp.zeros((0, D), dtype)
                             if compute_variance else None)
            conv_list.append(jnp.zeros(0, bool))
            iter_list.append(jnp.zeros(0, jnp.int32))
            continue
        total += E
        mask = None if active is None else active[b]
        if mask is not None:
            mask = np.asarray(mask, bool)
            if mask.shape != (E,):
                raise ValueError(
                    f"active mask for bucket {b} has shape {mask.shape}, "
                    f"expected ({E},)")
            if mask.all():
                mask = None  # full solve — take the unsliced path
        prev_var = None
        if compute_variance and mask is not None:
            prev_var = (upload(prev_variances[b], dtype)
                        if prev_variances is not None
                        and prev_variances[b] is not None else None)
        if mask is not None and not mask.any():
            # fully frozen bucket: nothing touches the device at all
            coeffs.append(w0_b)
            variances.append(
                None if not compute_variance else
                (prev_var if prev_var is not None
                 else jnp.zeros((E, D), dtype)))
            conv_list.append(jnp.ones(E, bool))
            iter_list.append(jnp.zeros(E, jnp.int32))
            continue
        sel = None if mask is None else np.flatnonzero(mask)
        n_solve = E if sel is None else len(sel)
        opt_b = resolve_re_optimizer(optimizer, D)
        n_dev = 1 if mesh is None else mesh.shape[axis]
        # Entities are independent, so a bucket too large for one
        # execution is solved in blocks of one shape (single compile)
        # whose size follows the bytes a block may hold
        bs = block_entities(
            n_solve, entity_bytes(N, k, D, itemsize, opt_b, config.history),
            n_dev)
        # dense Newton off the mesh keeps X between calls and runs the
        # solve alone; everything else goes through the one 10-argument
        # solver (densifying inside, where it is Newton)
        keep_dense = opt_b == "newton" and mesh is None
        if mesh is not None:
            run = _jitted_sharded_solver(D, task, opt_b, config,
                                         compute_variance, mesh, axis,
                                         norm_mode)
        elif keep_dense:
            densify, run = _jitted_newton_halves(D, task, config,
                                                 compute_variance, norm_mode)
        else:
            run = _jitted_placed_solver(D, task, opt_b, config,
                                        compute_variance, norm_mode)
        if local_norm is not None:
            f_loc, s_loc, pos = local_norm[b]
            s_arg = jnp.zeros((E, 1), dtype) if s_loc is None else s_loc
        else:  # unused dummies (dead-code-eliminated under jit)
            f_loc = s_arg = jnp.zeros((E, 1), dtype)
            s_loc = pos = None
        w_init = jnp.zeros((E, D), dtype) if w0_b is None else w0_b
        if local_norm is not None and w0_b is not None:
            w_init = _re_to_training_space(w_init, f_loc, s_loc, pos)
        offs = _gather_offsets(offsets, dev.sample_idx)
        if keep_dense:
            X = dev.dense.get((norm_mode, id(normalization)))
            if X is None:
                X = _densify_bucket(densify, dev, f_loc, s_arg, bs)
                if placed.dense_bytes + X.nbytes <= _RE_DENSE_KEEP_BYTES:
                    placed.dense_bytes += X.nbytes
                    dev.dense[(norm_mode, id(normalization))] = X
            per_entity = (X, dev.labels, dev.weights, offs, w_init)
            shared = (l2_dev,)
        else:
            tables = (dev.indices, dev.values)
            if mesh is not None:  # the sharded solver reads row-major
                tables = tuple(jnp.swapaxes(a, 1, 2) for a in tables)
            per_entity = tables + (dev.labels, dev.weights, offs, w_init,
                                   f_loc, s_arg)
            shared = (l2_dev, l1_dev)
        if sel is not None:
            # active-set sub-bucket: gather the unconverged entities on
            # the device, padded to the power-of-two ladder width
            width = _active_width(n_solve, bs, n_dev)
            sel_pad = np.full(max(width, n_solve), E, np.int32)
            sel_pad[:n_solve] = sel
            per_entity = _take_entities(per_entity, upload(sel_pad))
            bs = min(bs, len(sel_pad))
        with obs_trace.span("re.solve.bucket", cat="train",
                            effect=data.effect_name, bucket=b,
                            entities=n_solve, N=N, D=D, optimizer=opt_b):
            (W, V, conv, iters), n_blocks = _run_entity_blocks(
                run, per_entity, shared,
                n_solve if sel is None else len(sel_pad), bs)
        blocks_total += n_blocks
        if local_norm is not None:
            f_s, s_s, pos_s = f_loc, s_loc, pos
            if sel is not None:
                f_s, s_s, pos_s = (None if a is None else a[sel]
                                   for a in (f_loc, s_loc, pos))
                W = W[:n_solve]
            W = _re_to_model_space(W, f_s, s_s, pos_s)
        if sel is not None:
            # scatter solved entities back; frozen rows carry over
            sel_dev = upload(sel.astype(np.int32))
            W = w0_b.at[sel_dev].set(W[:n_solve])
            if compute_variance:
                V_full = (prev_var if prev_var is not None
                          else jnp.zeros((E, V.shape[1]), V.dtype))
                V = V_full.at[sel_dev].set(V[:n_solve])
            conv = jnp.ones(E, bool).at[sel_dev].set(conv[:n_solve])
            iters = jnp.zeros(E, jnp.int32).at[sel_dev].set(
                iters[:n_solve].astype(jnp.int32))
        # opt-in NaN trap at the batched per-entity solver's host
        # boundary (no-op unless a NaNGuard context is armed)
        nan_guard_check(f"re_solver:bucket{b}", W)
        if compute_variance:
            nan_guard_check(f"re_solver:bucket{b}:variances", V)
        coeffs.append(W)
        variances.append(V if compute_variance else None)
        conv_list.append(conv)
        iter_list.append(iters.astype(jnp.int32))
        solved_total += n_solve
    return RandomEffectFitResult(
        coefficients=coeffs,
        variances=variances if compute_variance else None,
        converged=conv_list,
        iterations=iter_list,
        entities=total,
        entities_solved=solved_total,
        blocks=blocks_total,
    )


def _densify_bucket(densify, dev: PlacedBucket, f_loc, s_loc, bs: int):
    """``X [E, D, N]`` of a whole bucket, built ``bs`` entities at a time
    (the scan's temporaries are of the block's size)."""
    (X,), _ = _run_entity_blocks(
        lambda *block: (densify(*block),),
        (dev.indices, dev.values, f_loc, s_loc), (), dev.indices.shape[0], bs)
    return X


# RE local dimensions are small by design; up to this width a row's
# margin is taken by comparing its slots with 0..D-1 (the densify's own
# form, element-wise and fused), past it by a gather per slot
_RE_COMPARE_MAX_DIM = 128


def _bucket_margins(W, idx_t, val_t):
    """[E, M] margins of a bucket's rows (slot-major ``[E, k, M]``) under
    their entities' models ``W [E, D]``."""
    D = W.shape[1]
    if D > _RE_COMPARE_MAX_DIM:
        return jax.vmap(lambda w, i, v: jnp.sum(v * w[i], axis=0))(
            W, idx_t, val_t)
    iota = jnp.arange(D, dtype=idx_t.dtype)[None, :, None]

    def add_slot(m, j):
        idx_j = jnp.take(idx_t, j, axis=1)[:, None, :]  # [E, 1, M]
        w_j = jnp.sum(jnp.where(idx_j == iota, W[:, :, None], 0.0), axis=1)
        return m + jnp.take(val_t, j, axis=1) * w_j, None

    E, _, M = idx_t.shape
    m, _ = jax.lax.scan(add_slot, jnp.zeros((E, M), W.dtype),
                        jnp.arange(idx_t.shape[1]))
    return m


# the rescoring programs hang on this key in ``data_parallel``'s runner
# cache, beside the fits (they belong to no objective)
_RESCORE_PROGRAMS = object()


def _rescore_program(n: int):
    def make():
        @jax.named_scope("photon.re/rescore")
        def rescore(buckets, coefficients):
            dt = coefficients[0].dtype
            scores = jnp.zeros((n + 1,), dt)  # slot n swallows padding
            for (idx, val, target), W in zip(buckets, coefficients):
                m = _bucket_margins(W, idx, val)
                scores = scores.at[target.reshape(-1)].add(
                    jnp.where(target < n, m, 0.0).reshape(-1))
            return scores[:n]
        return rescore

    from photon_ml_tpu.parallel.data_parallel import cached_jit

    return cached_jit(_RESCORE_PROGRAMS, ("re_rescore", n), make)


def score_random_effect(
    score_view,
    coefficients: Sequence,
    num_samples: int,
    dtype=jnp.float32,
    prev: Optional[jax.Array] = None,
    changed: Optional[Sequence[Optional[np.ndarray]]] = None,
) -> jax.Array:
    """Margins of every sample under its entity's model, scattered into a
    full-dataset score vector (the reference's CoordinateDataScores role,
    SURVEY.md §3.2). Samples with no entity model score 0. ``score_view``
    is a list of ``REScoreBucket`` (uploaded for this call) or the same
    placed once (``place_score_view``); the full form is one jitted
    program over all buckets.

    Incremental mode (``prev`` + ``changed``): recompute margins only for
    the rows owned by re-solved entities and scatter-overwrite them into
    the previous score vector — every row belongs to at most one entity
    per coordinate, so a plain set is exact. ``changed`` holds one boolean
    mask [E] per bucket (None = whole bucket changed); the changed rows
    are gathered on the device and padded to a power-of-two entity width so
    the margin kernel's shape ladder stays bounded as active sets shrink."""
    dtype = jnp.dtype(dtype)
    if not isinstance(score_view, PlacedScoreView):
        score_view = place_score_view(score_view, num_samples, dtype)
    coefficients = [upload(W, dtype) for W in coefficients]
    if prev is None or changed is None:
        if not score_view.buckets:
            return jnp.zeros((num_samples,), dtype)
        return _rescore_program(num_samples)(tuple(score_view.buckets),
                                             tuple(coefficients))

    scores = jnp.concatenate(
        [jnp.asarray(prev, dtype), jnp.zeros((1,), dtype)])
    for (idx, val, target), W, mask in zip(score_view.buckets, coefficients,
                                           changed):
        E = idx.shape[0]
        if E == 0:
            continue
        if mask is None:
            sel = np.arange(E)
        else:
            sel = np.flatnonzero(np.asarray(mask, bool))
            if len(sel) == 0:
                continue
        sel_pad = np.full(_active_width(len(sel), E, 1), E, np.int32)
        sel_pad[:len(sel)] = sel
        sel_dev = upload(sel_pad)
        W_s, idx_s, val_s = _take_entities((W, idx, val), sel_dev)
        tgt_s = jnp.take(target, sel_dev, axis=0, mode="fill",
                         fill_value=num_samples)
        m = _bucket_margins_jit(W_s, idx_s, val_s)
        # overwrite, don't add: these rows' previous margins are stale
        scores = scores.at[tgt_s.reshape(-1)].set(
            jnp.where(tgt_s < num_samples, m, 0.0).reshape(-1), mode="drop")
    return scores[:num_samples]


_bucket_margins_jit = jax.jit(_bucket_margins)
