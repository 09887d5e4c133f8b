"""The plain reference of the GLMix cells: numpy/scipy, float64.

It imports nothing of the program and takes nothing the program made. The
model is Zhang et al., "GLMix: Generalized Linear Mixed Models For
Large-Scale Response Prediction" (KDD 2016), sections 2-3: for user ``u``,
item ``i`` and row ``t``

    logit P(y_t = 1) = x_t . b  +  s_t . alpha_u  +  q_t . beta_i

with Gaussian priors (L2) on ``b``, every ``alpha_u`` and every ``beta_i``,
and the objective

    sum_t log(1 + exp(m_t)) - y_t m_t
      + l2_b/2 |b|^2 + l2_u/2 sum_u |alpha_u|^2 + l2_i/2 sum_i |beta_i|^2

fitted by block coordinate descent. One sweep: L-BFGS on ``b``
(``benchmark/reference.py``'s, a fresh history each sweep, from the
current ``b``) with the two random effects' scores as offsets; exact
Newton steps on every ``alpha_u`` (independent, each over that user's
rows, the other two terms as offsets), then on every ``beta_i``, each from
the current point, for the configuration's iteration caps.

Departures from the paper, both the program's: a Newton step is taken at
the first of the lengths 1, 1/2, 1/4, 1/8 that does not raise that
entity's objective, and an entity none of them serves stops (the paper
names no safeguard; the program also takes a step that raises the
objective by less than 4 ulps of it, which the float64 comparison here
has no need of); an entity's coefficients live in the effect's whole
feature space — a feature the entity never saw has a zero column, so its
coefficient stays at its start, which is what the program's per-entity
subspaces amount to.

Entities are solved batched: grouped by row count, a group's rows as a
dense ``[E, N, D]`` block, groups on a few threads. ``rounding`` puts a
lower precision in the reference's place (the control), ``fault`` plants a
fault (``FAULTS``): both are for the readings the limits are set from.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np
from scipy.special import expit

from benchmark import reference

FAULTS = (
    "stale_offsets",  # the per-item solve never sees the users' new scores
    "skip_group",  # one size group of users is left unsolved
    "half_rows",  # the heaviest user's solve sees half of its rows
)
_STEP_LENGTHS = (1.0, 0.5, 0.25, 0.125)
_GROUP_BYTES = 48 << 20  # a group's dense block, so that it stays in cache


class FixedBlock(reference.LogisticL2):
    """The fixed effect's objective: the other terms' scores as offsets."""

    offsets = 0.0

    def margins(self, v):
        return super().margins(v) + self.offsets


class RandomBlock:
    """One random effect: every entity's rows, dense, grouped by size."""

    def __init__(self, entity, indices, values, dim, labels,
                 num_entities: int):
        n, k = indices.shape
        self.dim, self.n = int(dim), n
        self.entity = np.asarray(entity)
        self.indices, self.values = indices, values
        counts = np.bincount(self.entity, minlength=num_entities)
        self.counts = counts
        order = np.argsort(self.entity, kind="stable")
        starts = np.cumsum(counts) - counts
        pos = np.empty(n, np.int64)
        pos[order] = np.arange(n) - np.repeat(starts, counts)
        # groups: entities of one octave of row count, cut to the byte cap
        present = np.flatnonzero(counts > 0)
        present = present[np.argsort(counts[present], kind="stable")]
        octave = np.ceil(np.log2(counts[present])).astype(np.int64)
        self.groups = []
        for o in np.unique(octave):
            members = present[octave == o]
            width = int(counts[members[-1]])  # the octave's largest
            cap = max(1, _GROUP_BYTES // (width * self.dim * 8))
            self.groups += [members[c:c + cap]
                            for c in range(0, len(members), cap)]
        group_of = np.full(num_entities, -1, np.int64)
        rank_of = np.zeros(num_entities, np.int64)
        for g, members in enumerate(self.groups):
            group_of[members] = g
            rank_of[members] = np.arange(len(members))
        row_group = group_of[self.entity]
        row_order = np.argsort(row_group, kind="stable")
        cuts = np.searchsorted(row_group[row_order],
                               np.arange(len(self.groups) + 1))
        self.blocks = []
        for g, members in enumerate(self.groups):
            rows = row_order[cuts[g]:cuts[g + 1]]
            E, N = len(members), int(counts[members].max())
            e, p = rank_of[self.entity[rows]], pos[rows]
            X = np.zeros((E, N, self.dim))
            for j in range(k):  # one slot of every row: no place twice
                X[e, p, indices[rows, j]] += values[rows, j]
            y = np.zeros((E, N))
            y[e, p] = labels[rows]
            live = np.zeros((E, N))
            live[e, p] = 1.0
            row_of = np.zeros((E, N), np.int64)
            row_of[e, p] = rows
            self.blocks.append((X, y, live, row_of))

    def scores(self, W):
        """x_t . w_entity(t) for every row."""
        out = np.zeros(self.n)
        for j in range(self.indices.shape[1]):
            out += W[self.entity, self.indices[:, j]] * self.values[:, j]
        return out

    def solve(self, W, offsets, l2: float, iterations: int, workers,
              r: Callable, skip_group: Optional[int] = None,
              half_rows_of: Optional[int] = None):
        """``iterations`` safeguarded Newton steps on every entity, from
        ``W`` [entities, dim]; returns the new ``W``."""
        W = W.copy()
        eye = np.eye(self.dim)

        def one(g):
            if g == skip_group:
                return
            members = self.groups[g]
            X, y, live, row_of = self.blocks[g]
            if half_rows_of is not None and half_rows_of in members:
                live = live.copy()
                at = int(np.flatnonzero(members == half_rows_of)[0])
                live[at, : self.counts[half_rows_of] // 2] = 0.0
            offs = offsets[row_of]
            w = r(W[members])

            def value(w):
                m = r(np.einsum("end,ed->en", X, w) + offs)
                per = (np.logaddexp(0.0, m) - y * m) * live
                return per.sum(axis=1) + 0.5 * l2 * (w * w).sum(axis=1)

            f = value(w)
            active = np.ones(len(members), bool)
            for _ in range(iterations):
                m = r(np.einsum("end,ed->en", X, w) + offs)
                p = expit(m)
                grad = r(np.einsum("end,en->ed", X, (p - y) * live)
                         + l2 * w)
                Xd = X * (p * (1.0 - p) * live)[..., None]
                H = r(np.matmul(Xd.transpose(0, 2, 1), X) + l2 * eye)
                step = r(np.linalg.solve(H, grad[..., None])[..., 0])
                taken = np.zeros(len(members), bool)
                for length in _STEP_LENGTHS:
                    trial = r(w - length * step)
                    f_trial = value(trial)
                    ok = active & ~taken & (f_trial <= f)
                    w = np.where(ok[:, None], trial, w)
                    f = np.where(ok, f_trial, f)
                    taken |= ok
                active &= taken  # an entity no length serves stops
            W[members] = w

        workers.map(one, range(len(self.groups)))
        return W


class Glmix:
    """The three blocks over one set of rows, and the sweep."""

    def __init__(self, rows, l2: dict, workers, users: int, items: int,
                 rounding: Optional[Callable] = None):
        self.workers = workers
        self.r = rounding if rounding is not None else (lambda x: x)
        self.l2 = {k: float(v) for k, v in l2.items()}
        self.y = np.asarray(rows.labels, np.float64)
        self.base = np.zeros(rows.n)
        self.fixed = FixedBlock(rows.global_indices, self.y, rows.dim,
                                self.l2["fixed"], workers,
                                rounding=rounding)
        it_idx, it_val, it_dim = rows.item_feats
        us_idx, us_val, us_dim = rows.user_feats
        self.user = RandomBlock(rows.user, it_idx, it_val.astype(np.float64),
                                it_dim, self.y, users)
        self.item = RandomBlock(rows.item, us_idx, us_val.astype(np.float64),
                                us_dim, self.y, items)
        self.dim = rows.dim

    def start(self):
        return {"fixed": np.zeros(self.dim),
                "user": np.zeros((len(self.user.counts), self.user.dim)),
                "item": np.zeros((len(self.item.counts), self.item.dim))}

    def scores(self, model: dict) -> dict:
        return {"fixed": self.fixed._x(model["fixed"]),
                "user": self.user.scores(model["user"]),
                "item": self.item.scores(model["item"])}

    def data_loss(self, total) -> float:
        return float(np.sum(np.logaddexp(0.0, total) - self.y * total))

    def penalty(self, model: dict) -> float:
        return sum(0.5 * self.l2[k] * float(np.sum(model[k] * model[k]))
                   for k in ("fixed", "user", "item"))

    def evaluate(self, model: dict) -> dict:
        """Scores, data loss and total objective at a model."""
        s = self.scores(model)
        total = self.base + s["fixed"] + s["user"] + s["item"]
        loss = self.data_loss(total)
        return {"scores": total, "data_loss": loss,
                "objective": loss + self.penalty(model)}

    def follow(self, sweeps: int, caps: dict, history: int = 10,
               max_line_search_steps: int = 25,
               fault: Optional[str] = None):
        """``sweeps`` sweeps from zero. -> (model, [record a sweep]): a
        record holds ``fixed_loss`` (the fixed block's objective after its
        fit), ``data_loss``, ``objective`` and the ``model`` after the
        sweep."""
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"no fault {fault!r} (has {FAULTS})")
        model = self.start()
        s = {k: np.zeros_like(self.base) for k in ("fixed", "user", "item")}
        heaviest = int(np.argmax(self.user.counts))
        records = []
        for _ in range(sweeps):
            self.fixed.offsets = self.base + s["user"] + s["item"]
            b, losses, _ = reference.lbfgs_steps(
                self.fixed, model["fixed"], caps["fixed"], history=history,
                max_line_search_steps=max_line_search_steps)
            model["fixed"] = b
            s["fixed"] = self.fixed._x(b)
            stale_user = s["user"]
            model["user"] = self.user.solve(
                model["user"], self.base + s["fixed"] + s["item"],
                self.l2["user"], caps["user"], self.workers, self.r,
                skip_group=(len(self.user.groups) // 2
                            if fault == "skip_group" else None),
                half_rows_of=heaviest if fault == "half_rows" else None)
            s["user"] = self.user.scores(model["user"])
            seen = stale_user if fault == "stale_offsets" else s["user"]
            model["item"] = self.item.solve(
                model["item"], self.base + s["fixed"] + seen,
                self.l2["item"], caps["item"], self.workers, self.r)
            s["item"] = self.item.scores(model["item"])
            # the record is the follower's own: in the control's place its
            # loss is summed from rounded scores and handed back rounded
            loss = float(self.r(self.data_loss(self.r(
                self.base + s["fixed"] + s["user"] + s["item"]))))
            records.append({"fixed_loss": losses[-1], "data_loss": loss,
                            "objective": loss + self.penalty(model),
                            "model": {k: v.copy() for k, v in model.items()}})
        return model, records
