"""Runner ``glm_fit``: whole fixed-effect fits, back to back.

The rows lie in HBM, laid out by the program's ``shard_batch`` over
``make_mesh(traffic["mesh"])``, with the column-sorted view the program
precomputes (``build_csc``) where its ``sparse_grad="auto"`` resolves to a
CSC path. A piece of the window is one call of
``photon_ml_tpu.parallel.data_parallel.fit_distributed`` — the call under
``cli/glm_driver.py`` — under an iteration cap with ``tolerance=0``, from
``w0 = w0_step * i``, closed by fetching the result's scalars to the host.
Set-up drives the same call once (``i = 0``), which compiles or loads every
program the window uses.

``check`` compares the last fit the window finished with the plain
reference (``benchmark/reference.py``), which follows the whole fit from the
same ``w0`` and evaluates its objective at the program's ``w``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import data, reference


def relative_gap(got: float, want: float) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-300)


class Runner:
    def __init__(self, cell, seed: int):
        cfg = cell.config
        self.cfg, self.seed = cfg, seed
        self.mesh_axes = dict(cell.traffic["mesh"])
        self.chips = cell.chips
        self.rows_per_chip = 1 << int(cfg["rows_per_chip_log2"])
        self.rows = self.rows_per_chip * self.chips
        self.dim = 1 << int(cfg["feature_buckets_log2"])
        self.k = int(cfg["features_per_row"])
        self.cap = int(cfg["passes_per_fit"])
        self.w0_step = float(cell.traffic["w0_step"])
        self.first_steps = min(int(cell.traffic["check_steps"]), self.cap)
        self.phases = {}
        self.last = None

    def shapes(self) -> dict:
        return {"rows": self.rows, "rows_per_chip": self.rows_per_chip,
                "k": self.k, "dim": self.dim, "passes_per_fit": self.cap}

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
        self.indices, self.labels = data.criteo_rows(
            self.rows, self.dim, self.k, int(cfg["data_seed"]), self.seed)
        self._phase("data_s", t)

        t = time.perf_counter()
        self.dp = data_parallel
        self.mesh = make_mesh(self.mesh_axes,
                              devices=jax.devices()[:self.chips])
        self.objective = make_objective(cfg["loss"])
        dtype = jnp.dtype(cfg["dtype"])
        batch = LabeledBatch(
            SparseFeatures(self.indices, None, dim=self.dim),
            np.asarray(self.labels, dtype), np.zeros((self.rows,), dtype),
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

        self.opt_config = OptimizerConfig(
            max_iters=self.cap, tolerance=0.0, history=int(cfg["history"]),
            max_line_search_steps=int(cfg["max_line_search_steps"]))
        step, dim = self.w0_step, self.dim
        self.make_w0 = jax.jit(
            lambda i: jnp.full((dim,), step, dtype) * i.astype(dtype))
        t = time.perf_counter()
        self.fit(0)
        self._phase("first_fit_s", t)

    def _phase(self, name: str, t0: float) -> None:
        self.phases[name] = time.perf_counter() - t0

    # -- the timed call ---------------------------------------------------
    def fit(self, i: int) -> dict:
        """One whole fit through the program's entry, closed by a scalar
        fetch (the read cannot complete before the fit has run)."""
        cfg = self.cfg
        t0 = time.perf_counter()
        res = self.dp.fit_distributed(
            self.objective, self.batch, self.mesh,
            self.make_w0(np.int32(i)), l2=float(cfg["l2"]),
            optimizer=cfg["optimizer"], config=self.opt_config,
            sparse_grad=cfg["sparse_grad"], line_search=cfg["line_search"],
            precomputed_csc=self.csc)
        passes, value = int(res.iterations), float(res.value)
        t1 = time.perf_counter()
        self.last = (i, res)
        return {"t0": t0, "t1": t1, "passes": passes, "value": value, "i": i}

    def window(self, seconds: float) -> dict:
        pieces = []
        start = time.perf_counter()
        i = 1
        while time.perf_counter() - start < seconds:
            pieces.append(self.fit(i))
            i += 1
        return {"start": start, "end": pieces[-1]["t1"], "pieces": pieces,
                "rows": self.rows}

    def describe(self, window: dict) -> dict:
        return {"passes": [p["passes"] for p in window["pieces"]],
                "fit_s": [p["t1"] - p["t0"] for p in window["pieces"]],
                "sparse_grad": self.sparse_grad}

    # -- after the window -------------------------------------------------
    def release(self) -> None:
        """Fetch what the comparison reads, then drop the device state."""
        i, res = self.last
        self.fetched = {
            "i": i, "w": np.asarray(res.w, np.float64),
            "value": float(res.value), "grad_norm": float(res.grad_norm),
            "iterations": int(res.iterations),
            "loss_history": np.asarray(res.loss_history, np.float64),
            "grad_norm_history": np.asarray(res.grad_norm_history,
                                            np.float64)}
        self.last = self.batch = self.csc = None

    def reference_objective(self, workers, **kw):
        return reference.LogisticL2(self.indices, self.labels, self.dim,
                                    float(self.cfg["l2"]), workers, **kw)

    def reference_fit(self, obj, w0):
        """The reference follows the whole fit: ``passes_per_fit`` steps."""
        if self.cfg["optimizer"] == "tron":
            return reference.tron_steps(obj, w0, self.cap)[:3]
        return reference.lbfgs_steps(
            obj, w0, self.cap, history=int(self.cfg["history"]),
            max_line_search_steps=int(self.cfg["max_line_search_steps"]))

    def start_point(self, i: int):
        return np.full((self.dim,), self.w0_step * i, np.float64)

    def check(self, window: dict):
        """-> ({number: value}, attempted, failed)."""
        got = self.fetched
        attempted = len(window["pieces"])
        failed = sum(1 for p in window["pieces"]
                     if not np.isfinite(p["value"]) or p["passes"] < 1)
        with reference.Workers() as workers:
            obj = self.reference_objective(workers)
            w0 = self.start_point(got["i"])
            numbers = compare(got, obj, w0, self.reference_fit(obj, w0),
                              self.first_steps)
        return numbers, attempted, failed


def compare(got: dict, obj, w0, followed, first_steps: int) -> dict:
    """The numbers of ``correct``: the program's fit (``got``) against the
    reference's (``followed`` = its w, losses and gradient norms step by
    step, from the same ``w0``) and against the reference's objective at the
    program's own ``w``. Gaps of norms and of losses, relative to the
    reference's."""
    w_ref, losses, gnorms = followed
    par = obj.par
    numbers = {}
    for s in range(first_steps):
        numbers[f"loss_step{s + 1}_gap"] = relative_gap(
            got["loss_history"][s], losses[s])
    numbers["grad_step1_gap"] = relative_gap(got["grad_norm_history"][0],
                                             gnorms[0])
    numbers["fit_loss_gap"] = relative_gap(got["value"], losses[-1])
    change_ref = par.norm(par.axpy(par.copy(w_ref), -1.0, w0))
    change_got = par.norm(par.axpy(par.copy(got["w"]), -1.0, w0))
    numbers["fit_change_gap"] = relative_gap(change_got, change_ref)
    f_ref, g_ref = obj.value_grad(got["w"])
    numbers["final_loss_gap"] = relative_gap(got["value"], f_ref)
    numbers["final_grad_gap"] = relative_gap(got["grad_norm"],
                                             par.norm(g_ref))
    return numbers
