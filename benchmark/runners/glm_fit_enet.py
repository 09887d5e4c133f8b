"""Runner ``glm_fit_enet``: whole elastic-net fits by OWL-QN, back to back.

``glm_fit``'s runner (same rows, same lay-out, same precomputed CSC view,
same closed loop) with the three things an L1 term changes: the call hands
``fit_distributed`` the ``l1`` and ``l2`` that
``RegularizationContext(regularization, elastic_net_alpha)`` makes of the
configuration's ``regularization_weight`` — the arithmetic under
``cli/glm_driver.py`` — with ``optimizer="owlqn"``; a piece has failed if
its value is not finite, its search stalled (passes under the cap) or its
``w`` is all zero; and ``check`` follows the whole last fit with the plain
OWL-QN of ``benchmark/reference_owlqn.py``: the full objective in the
loss's place, the pseudo-gradient in the gradient's, and the count and the
places of the coefficients that are exactly zero.
"""

from __future__ import annotations

import os
import statistics
import time
from types import SimpleNamespace

import numpy as np

from benchmark import harness, reference, reference_owlqn

glm_fit = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "glm_fit.py"))


class Runner(glm_fit.Runner):
    def __init__(self, cell, seed: int):
        super().__init__(cell, seed)
        from photon_ml_tpu.ops.regularization import RegularizationContext

        reg = RegularizationContext(self.cfg["regularization"],
                                    float(self.cfg["elastic_net_alpha"]))
        weight = float(self.cfg["regularization_weight"])
        self.l1, self.l2 = reg.l1_weight(weight), reg.l2_weight(weight)

    # -- the timed call ---------------------------------------------------
    def fit(self, i: int) -> dict:
        cfg = self.cfg
        t0 = time.perf_counter()
        res = self.dp.fit_distributed(
            self.objective, self.batch, self.mesh,
            self.make_w0(np.int32(i)), l1=self.l1, l2=self.l2,
            optimizer=cfg["optimizer"], config=self.opt_config,
            sparse_grad=cfg["sparse_grad"], line_search=cfg["line_search"],
            precomputed_csc=self.csc)
        passes, value = int(res.iterations), float(res.value)
        t1 = time.perf_counter()
        # the two counters this configuration's PR added to the program; a
        # program without them reports neither
        trials = getattr(res, "line_search_trials", None)
        nonzeros = getattr(res, "nonzeros", None)
        self.last = (i, res)
        return {"t0": t0, "t1": t1, "passes": passes, "value": value, "i": i,
                "trials": None if trials is None else int(trials),
                "nonzeros": None if nonzeros is None else int(nonzeros)}

    def describe(self, window: dict) -> dict:
        pieces = window["pieces"]
        nonzeros = [p["nonzeros"] for p in pieces
                    if p["nonzeros"] is not None]
        per_pass = [p["trials"] / p["passes"] for p in pieces
                    if p["trials"] is not None and p["passes"] > 0]
        return {**super().describe(window), "l1": self.l1, "l2": self.l2,
                "trials": [p["trials"] for p in pieces],
                "nonzeros": [p["nonzeros"] for p in pieces],
                "nonzeros_median": (statistics.median(nonzeros)
                                    if nonzeros else None),
                "trials_per_pass_median": (statistics.median(per_pass)
                                           if per_pass else None)}

    # -- after the window -------------------------------------------------
    def reference_objective(self, workers, **kw):
        return reference_owlqn.ElasticNet(
            reference.LogisticL2(self.indices, self.labels, self.dim,
                                 self.l2, workers, **kw), self.l1)

    def reference_fit(self, obj, w0, **faults):
        """-> (w, [F a step], [|pseudo-gradient| a step], [trials a step])
        of the whole fit, ``passes_per_fit`` steps."""
        return reference_owlqn.owlqn_steps(
            obj, w0, self.cap, history=int(self.cfg["history"]),
            max_line_search_steps=int(self.cfg["max_line_search_steps"]),
            **faults)

    def check(self, window: dict):
        """-> ({number: value}, attempted, failed)."""
        got = self.fetched
        attempted = len(window["pieces"])
        failed = sum(1 for p in window["pieces"]
                     if not np.isfinite(p["value"]) or p["passes"] < self.cap
                     or p["nonzeros"] == 0)
        if not np.any(got["w"]):
            failed = max(failed, 1)
        with reference.Workers() as workers:
            obj = self.reference_objective(workers)
            w0 = self.start_point(got["i"])
            numbers = compare(got, obj, w0, self.reference_fit(obj, w0),
                              self.first_steps)
        return numbers, attempted, failed


def compare(got: dict, obj, w0, followed, first_steps: int) -> dict:
    """``glm_fit.compare``'s numbers with the full objective in the loss's
    place and the pseudo-gradient in the gradient's, and two of this
    configuration's own: ``fit_nonzero_gap`` (the counts of nonzero
    coefficients, the program's against the reference's, over the
    reference's) and ``fit_support_gap`` (coefficients that are exactly zero
    in one of the two final ``w`` and not in the other, over the reference's
    nonzeros)."""
    w_ref = followed[0]
    as_smooth = SimpleNamespace(par=obj.par,
                                value_grad=obj.value_pseudo_gradient)
    numbers = glm_fit.compare(got, as_smooth, w0, followed[:3], first_steps)
    mine, theirs = got["w"] != 0, w_ref != 0
    nonzeros = max(int(np.count_nonzero(theirs)), 1)
    numbers["fit_nonzero_gap"] = abs(
        int(np.count_nonzero(mine)) - nonzeros) / nonzeros
    numbers["fit_support_gap"] = int(np.count_nonzero(mine != theirs)
                                     ) / nonzeros
    return numbers
