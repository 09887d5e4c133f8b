"""Runner ``game_cd``: whole GLMix coordinate-descent runs, back to back.

The rows (``benchmark/data_game.py``) become a ``GameDataset`` with three
feature shards and two id columns. A piece of the window is one
``photon_ml_tpu.game.descent.CoordinateDescent(...).run(train)`` — the call
under ``GameEstimator.fit`` and ``photon-game-train`` — over one
``dataset_cache``, from zero coefficients, ``sweeps_per_piece`` sweeps of
fixed effect -> per-user -> per-item, with L2 weight ``1 + l2_step * j`` on
every block in piece ``j`` (a grid point; the weights are arguments of the
programs, so nothing compiles). The run returns its model and its history
on the host, which closes the piece. Set-up drives the same call once
(``j = 0``): it regroups the entities, places every table in device
memory and compiles or loads every program the window uses.

``check`` compares the last piece the window finished with the plain
reference (``benchmark/reference_game.py``), which follows that piece —
every sweep, that piece's L2 — from the same start and evaluates its
objective at the program's model.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import data_game, reference, reference_game
from benchmark.runners.glm_fit import relative_gap

# what every cell of this runner asks of the program: without it (a tree
# from before the tables lived on the device) the cell's size is out of
# reach, and the run says so at once instead of trying
_NEEDS = ("place_random_effect", "place_score_view")


def norm_gap(got: np.ndarray, want: np.ndarray, start=0.0) -> float:
    """|got - want| over |want - start|."""
    return float(np.linalg.norm(np.ravel(got - want))
                 / max(np.linalg.norm(np.ravel(want - start)), 1e-300))


def model_arrays(model, names: dict, users: int, items: int, dims: dict):
    """The program's ``GameModel`` as the reference keeps a model: the
    fixed vector, and one row an entity in the effect's whole feature
    space (an entity's subspace scattered through its projection)."""
    out = {"fixed": np.asarray(
        model.coordinates[names["fixed"]].model.coefficients.means,
        np.float64)}
    for key, count in (("user", users), ("item", items)):
        W = np.zeros((count, dims[key]))
        for bucket in model.coordinates[names[key]].buckets:
            ids = np.asarray(bucket.entity_ids, np.int64)
            proj = np.asarray(bucket.projection)
            coef = np.asarray(bucket.coefficients, np.float64)
            e, slot = np.nonzero(proj >= 0)
            W[ids[e], proj[e, slot]] = coef[e, slot]
        out[key] = W
    return out


class Runner:
    def __init__(self, cell, seed: int):
        from photon_ml_tpu.game import random_effect

        missing = [n for n in _NEEDS if not hasattr(random_effect, n)]
        if missing:
            raise SystemExit(
                "runner game_cd: this program keeps no random-effect tables "
                f"on the device (no {missing} in game/random_effect.py); "
                "the cell cannot run on it")
        cfg = cell.config
        self.cfg, self.seed = cfg, seed
        self.rows_n = 1 << int(cfg["rows_log2"])
        self.users, self.items = int(cfg["users"]), int(cfg["items"])
        self.sweeps = int(cell.traffic["sweeps_per_piece"])
        self.l2_step = float(cell.traffic["l2_step"])
        self.caps = {"fixed": int(cfg["fixed_iterations"]),
                     "user": int(cfg["random_iterations"]),
                     "item": int(cfg["random_iterations"])}
        self.names = {"fixed": "fixed", "user": "per-user",
                      "item": "per-item"}
        self.phases = {}
        self.last = None

    def shapes(self) -> dict:
        it_idx, _, it_dim = self.rows.item_feats
        us_idx, _, us_dim = self.rows.user_feats
        return {"rows": self.rows_n, "users": self.users,
                "items": self.items,
                "fixed_fields": int(self.cfg["fixed_fields"]),
                "fixed_iterations": self.caps["fixed"],
                "random_iterations": self.caps["user"],
                "user_dim": it_dim, "item_dim": us_dim,
                "user_slots": it_idx.shape[1], "item_slots": us_idx.shape[1],
                "sweeps_per_piece": self.sweeps}

    # -- set-up -----------------------------------------------------------
    def _phase(self, name: str, t0: float) -> None:
        self.phases[name] = time.perf_counter() - t0

    def l2_of(self, j: int) -> float:
        return float(self.cfg["l2"]) * (1.0 + self.l2_step * j)

    def coordinate_configs(self, j: int):
        from photon_ml_tpu.game.descent import CoordinateConfig

        cfg, l2 = self.cfg, self.l2_of(j)
        random = dict(
            coordinate_type="random", optimizer=cfg["random_optimizer"],
            max_iters=self.caps["user"], tolerance=0.0, reg_type="l2",
            reg_weight=l2, active_set=bool(cfg["active_set"]),
            projection=cfg["projection"],
            num_buckets=int(cfg["num_buckets"]))
        return [
            CoordinateConfig(
                name=self.names["fixed"], coordinate_type="fixed",
                feature_shard="global", optimizer=cfg["fixed_optimizer"],
                max_iters=self.caps["fixed"], tolerance=0.0, reg_type="l2",
                reg_weight=l2, sparse_grad=cfg["sparse_grad"]),
            CoordinateConfig(name=self.names["user"],
                             feature_shard="item_feats",
                             entity_column="user", **random),
            CoordinateConfig(name=self.names["item"],
                             feature_shard="user_feats",
                             entity_column="item", **random),
        ]

    def setup(self) -> None:
        t = time.perf_counter()
        import jax.numpy as jnp

        from photon_ml_tpu.game import descent
        from photon_ml_tpu.game.data import HostSparse
        from photon_ml_tpu.obs.metrics import training_metrics

        self._phase("import_program_s", t)
        t = time.perf_counter()
        self.rows = rows = data_game.glmix_rows(self.cfg, self.seed)
        self._phase("data_s", t)

        t = time.perf_counter()
        it_idx, it_val, it_dim = rows.item_feats
        us_idx, us_val, us_dim = rows.user_feats
        self.train = descent.make_game_dataset(
            {"global": HostSparse(rows.global_indices, None, rows.dim),
             "item_feats": HostSparse(it_idx, it_val, it_dim),
             "user_feats": HostSparse(us_idx, us_val, us_dim)},
            rows.labels, entity_ids={"user": rows.user, "item": rows.item})
        self.descent = descent
        self.dtype = jnp.dtype(self.cfg["dtype"])
        self.cache = {}
        self.metrics = training_metrics()
        self._phase("dataset_s", t)

        t = time.perf_counter()
        self.piece(0)
        self._phase("first_run_s", t)

    # -- the timed call ---------------------------------------------------
    def piece(self, j: int) -> dict:
        """One whole run through the program's entry: regroup, place and
        compile on the first call, nothing but the sweeps after."""
        t0 = time.perf_counter()
        cd = self.descent.CoordinateDescent(
            self.coordinate_configs(j), task="logistic",
            n_iterations=self.sweeps, dtype=self.dtype,
            dataset_cache=self.cache)
        model, history = cd.run(self.train)
        t1 = time.perf_counter()
        self.last = (j, model, history)
        losses = [r["train_loss"] for r in history if "train_loss" in r]
        return {"t0": t0, "t1": t1, "passes": len(losses),
                "value": losses[-1] if losses else float("nan"), "j": j}

    def window(self, seconds: float) -> dict:
        pieces = []
        start = time.perf_counter()
        j = 1
        while time.perf_counter() - start < seconds:
            pieces.append(self.piece(j))
            j += 1
        return {"start": start, "end": pieces[-1]["t1"], "pieces": pieces,
                "rows": self.rows_n,
                # the program's own records of the window's sweeps, for the
                # readers of the per-layer metrics
                "sweeps": self.metrics.sweep_records()[
                    -sum(p["passes"] for p in pieces):]}

    def describe(self, window: dict) -> dict:
        return {"passes": [p["passes"] for p in window["pieces"]],
                "piece_s": [p["t1"] - p["t0"] for p in window["pieces"]],
                "train_loss": [p["value"] for p in window["pieces"]]}

    # -- after the window -------------------------------------------------
    def release(self) -> None:
        """Bring what the comparison reads to the host, then drop the
        device state."""
        j, model, history = self.last
        it_dim, us_dim = self.rows.item_feats[2], self.rows.user_feats[2]
        self.fetched = {
            "j": j,
            "model": model_arrays(model, self.names, self.users, self.items,
                                  {"user": it_dim, "item": us_dim}),
            "fixed_loss": [r["loss"] for r in history
                           if r["coordinate"] == self.names["fixed"]],
            "train_loss": [r["train_loss"] for r in history
                           if "train_loss" in r]}
        self.last = self.train = self.cache = None

    def reference(self, workers, j: int, **kw):
        l2 = self.l2_of(j)
        return reference_game.Glmix(
            self.rows, {"fixed": l2, "user": l2, "item": l2}, workers,
            self.users, self.items, **kw)

    def check(self, window: dict):
        """-> ({number: value}, attempted, failed)."""
        attempted = len(window["pieces"])
        failed = sum(1 for p in window["pieces"]
                     if not np.isfinite(p["value"])
                     or p["passes"] != self.sweeps)
        with reference.Workers() as workers:
            ref = self.reference(workers, self.fetched["j"])
            followed = ref.follow(self.sweeps, self.caps)
            numbers = compare(self.fetched, ref, followed)
        return numbers, attempted, failed


def compare(got: dict, ref, followed) -> dict:
    """The numbers of ``correct``: the program's run (``got``: its model,
    its fixed-effect loss and its training loss after each sweep) against
    the reference's (``followed`` = its model and its records sweep by
    sweep, from the same start) and against the reference's objective at
    the program's own model. Gaps relative to the reference's."""
    model_ref, records = followed
    numbers = {}
    for s, rec in enumerate(records):
        numbers[f"fixed_loss_sweep{s + 1}_gap"] = relative_gap(
            got["fixed_loss"][s], rec["fixed_loss"])
        numbers[f"train_loss_sweep{s + 1}_gap"] = relative_gap(
            got["train_loss"][s], rec["data_loss"])
    start = ref.start()
    for key in ("fixed", "user", "item"):
        numbers[f"{key}_change_gap"] = norm_gap(
            got["model"][key], model_ref[key], start[key])
    at_ref = ref.evaluate(model_ref)
    at_got = ref.evaluate(got["model"])
    numbers["score_gap"] = norm_gap(at_got["scores"], at_ref["scores"])
    numbers["objective_gap"] = relative_gap(at_got["objective"],
                                            at_ref["objective"])
    # the program's own last record against the reference's loss at the
    # program's own point: rounding alone
    numbers["final_loss_gap"] = relative_gap(got["train_loss"][-1],
                                             at_got["data_loss"])
    return numbers
