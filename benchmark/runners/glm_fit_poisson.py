"""Runner ``glm_fit_poisson``: whole Poisson fits by TRON, back to back.

``glm_fit``'s runner (same feature rows, same lay-out, same precomputed CSC
view, same closed loop) with what a count model changes: the batch carries
the counts of ``benchmark/data_poisson.py`` as labels and their
log-exposures as ``offsets`` (``setup`` is overridden whole: ``glm_fit``'s
builds its batch with binary labels and zero offsets in one method, and a
warm-up fit on that batch would be a fit of another model); the call is
``fit_distributed(make_objective("poisson"), ..., optimizer="tron",
precomputed_csc=...)`` under ``OptimizerConfig(max_iters=passes_per_fit,
tolerance=0)``; a piece has failed if its value is not finite, its passes
are under the cap, or no step of it was accepted; and ``check`` follows the
whole last fit with ``reference.tron_steps`` over
``reference_poisson.PoissonL2`` and compares, beside ``glm_fit.compare``'s
numbers, the trust region's decisions: how many steps were accepted and how
many CG steps the fit took.

TRON's three counters (``cg_steps``, ``rejected_steps``,
``precond_passes``) are read from the result where the program has them; a
program without them gives ``None`` for each, the CG steps then come from
the product counter (``gather_products - 1 - iterations``) and the accepted
steps from the loss history.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import data_poisson, harness, reference, reference_poisson

glm_fit = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "glm_fit.py"))

_COUNTERS = ("cg_steps", "rejected_steps", "precond_passes")


def accepted_steps(losses, f_start: float, rel: float = 0.0) -> int:
    """Steps that moved the loss: a refused step hands back the loss before
    it, to the bit (``rel`` = 0), or to float32's rounding of the reference's
    starting value where the program's own is not known."""
    before = np.concatenate([[f_start], np.asarray(losses, np.float64)[:-1]])
    return int(np.count_nonzero(
        np.abs(np.asarray(losses, np.float64) - before)
        > rel * np.abs(before)))


class Runner(glm_fit.Runner):
    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        t_import = time.perf_counter()
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.ops.objective import make_objective
        from photon_ml_tpu.optimize import OptimizerConfig
        from photon_ml_tpu.parallel import data_parallel
        from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch
        from photon_ml_tpu.types import LabeledBatch, SparseFeatures

        cfg = self.cfg
        self._phase("import_program_s", t_import)
        t = time.perf_counter()
        self.draw()
        self._phase("data_s", t)

        t = time.perf_counter()
        self.dp = data_parallel
        self.mesh = make_mesh(self.mesh_axes,
                              devices=jax.devices()[:self.chips])
        self.objective = make_objective(cfg["loss"])
        dtype = jnp.dtype(cfg["dtype"])
        batch = LabeledBatch(
            SparseFeatures(self.indices, None, dim=self.dim),
            np.asarray(self.labels, dtype), np.asarray(self.offsets, dtype),
            np.ones((self.rows,), dtype))
        self.batch = jax.block_until_ready(shard_batch(batch, self.mesh))
        self.sparse_grad = data_parallel.resolve_sparse_grad(
            cfg["sparse_grad"], self.batch.features)
        self._phase("place_s", t)

        t = time.perf_counter()
        self.csc = None
        if self.sparse_grad.startswith("csc"):
            self.csc = jax.block_until_ready(data_parallel.build_csc(
                self.objective, self.batch, self.mesh))
        self._phase("build_csc_s", t)

        self.opt_config = OptimizerConfig(max_iters=self.cap, tolerance=0.0)
        step, dim = self.w0_step, self.dim
        self.make_w0 = jax.jit(
            lambda i: jnp.full((dim,), step, dtype) * i.astype(dtype))
        t = time.perf_counter()
        self.fit(0)
        self._phase("first_fit_s", t)

    def draw(self) -> None:
        """The rows from the two seeds: features, counts, log-exposures."""
        cfg = self.cfg
        drawn = cfg["counts"]
        self.indices, self.labels, self.offsets = data_poisson.poisson_rows(
            self.rows, self.dim, self.k, int(cfg["data_seed"]), self.seed,
            float(drawn["w_scale"]), float(drawn["log_exposure_mean"]),
            float(drawn["log_exposure_sd"]))

    # -- the timed call ---------------------------------------------------
    def fit(self, i: int) -> dict:
        cfg = self.cfg
        t0 = time.perf_counter()
        res = self.dp.fit_distributed(
            self.objective, self.batch, self.mesh,
            self.make_w0(np.int32(i)), l2=float(cfg["l2"]),
            optimizer=cfg["optimizer"], config=self.opt_config,
            sparse_grad=cfg["sparse_grad"], precomputed_csc=self.csc)
        passes, value = int(res.iterations), float(res.value)
        t1 = time.perf_counter()
        self.last = (i, res)
        piece = {"t0": t0, "t1": t1, "passes": passes, "value": value, "i": i}
        for name in _COUNTERS:
            count = getattr(res, name, None)
            piece[name] = None if count is None else int(count)
        return piece

    def describe(self, window: dict) -> dict:
        pieces = window["pieces"]
        return {**super().describe(window),
                **{name: [p[name] for p in pieces] for name in _COUNTERS}}

    # -- after the window -------------------------------------------------
    def release(self) -> None:
        _, res = self.last
        products = int(res.gather_products)
        counters = {name: getattr(res, name, None) for name in _COUNTERS}
        super().release()
        got = self.fetched
        got["cg_steps"] = (products - 1 - got["iterations"]
                           if counters["cg_steps"] is None
                           else int(counters["cg_steps"]))
        got["accepted_steps"] = (
            None if counters["rejected_steps"] is None
            else got["iterations"] - int(counters["rejected_steps"]))

    def reference_objective(self, workers, **kw):
        return reference_poisson.PoissonL2(
            self.indices, self.labels, self.offsets, self.dim,
            float(self.cfg["l2"]), workers, **kw)

    def reference_fit(self, obj, w0):
        """-> (w, [loss a step], [|grad| a step], [CG steps a step]) of the
        whole fit, ``passes_per_fit`` outer iterations."""
        return reference.tron_steps(obj, w0, self.cap)

    def check(self, window: dict):
        """-> ({number: value}, attempted, failed)."""
        got = self.fetched
        attempted = len(window["pieces"])
        failed = sum(1 for p in window["pieces"]
                     if not np.isfinite(p["value"]) or p["passes"] < self.cap
                     or (p["rejected_steps"] is not None
                         and p["rejected_steps"] >= p["passes"]))
        with reference.Workers() as workers:
            obj = self.reference_objective(workers)
            w0 = self.start_point(got["i"])
            numbers = compare(got, obj, w0, self.reference_fit(obj, w0),
                              self.first_steps)
        return numbers, attempted, failed


def compare(got: dict, obj, w0, followed, first_steps: int) -> dict:
    """``glm_fit.compare``'s numbers with the Poisson objective (a refused
    step's loss is the loss before it, on both sides) and two of this
    configuration's own: ``fit_accepted_gap`` (the steps the trust region
    accepted, the program's against the reference's, over the steps taken)
    and ``fit_cg_gap`` (the CG steps of the whole fit against the
    reference's, relative). ``benchmark/reference.tron_steps`` reports no
    decision, so the reference's accepted steps are read off its losses."""
    _, losses, _, cg = followed
    numbers = glm_fit.compare(got, obj, w0, followed[:3], first_steps)
    f_start = obj.value_grad(w0)[0]
    theirs = accepted_steps(losses, f_start)
    mine = got.get("accepted_steps")
    if mine is None:  # a program without the counter: off its own history
        mine = accepted_steps(got["loss_history"][:len(losses)], f_start,
                              rel=1e-4)
    numbers["fit_accepted_gap"] = abs(mine - theirs) / len(losses)
    numbers["fit_cg_gap"] = glm_fit.relative_gap(got["cg_steps"], sum(cg))
    return numbers
