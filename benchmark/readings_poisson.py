"""The upper readings ``criteo-poisson-tron.fit``'s limits are set from (not
part of a run; host work, no chip): ``benchmark/readings.py``'s control and
faults for the Poisson runner, whose faults are its own.

    python3 benchmark/readings_poisson.py --seeds 1,2,3 \
        --what control,faults --out <file.jsonl>

The lower readings are the cell's own runs on the chip: each prints the
numbers of its window's last fit (``compared``). Here, for each seed, at the
cell's own size, the float64 reference follows the fit from
``w0 = w0_step * --fit`` and stands against

* ``control``: itself with every vector the objective reads or hands back
  rounded to bfloat16, in the program's place;
* ``faults``, planted in the reference, in the program's place: the
  offsets left out (every exposure 1); the logistic loss's second
  derivative ``s (1 - s)`` where the Poisson's ``exp(m)`` belongs, in the
  Hessian-vector product and in the Jacobi diagonal; half of the batch left
  out and the rest counted twice. A state left unchanged reads 1 on
  ``fit_change_gap`` and on ``fit_accepted_gap`` (or 5/6, where the
  reference refuses a step itself) and needs no run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
from scipy.special import expit  # noqa: E402

from benchmark import harness, reference, reference_poisson  # noqa: E402

poisson = harness.load_module(os.path.join(
    harness.BENCH_DIR, "runners", "glm_fit_poisson.py"))


class LogisticCurvature(reference_poisson.PoissonL2):
    """The fault: another loss's second derivative under the Poisson's
    value and gradient."""

    def d2(self, w):
        s = expit(self.eta(w))
        return s * (1.0 - s)


def stand_in(runner, obj, w0):
    """What the program would have reported had it computed like ``obj``:
    the whole fit followed, its end as the fit's result."""
    w, losses, gnorms, cg = runner.reference_fit(obj, w0)
    return {"w": w, "value": losses[-1], "grad_norm": gnorms[-1],
            "loss_history": np.asarray(losses),
            "grad_norm_history": np.asarray(gnorms), "cg_steps": sum(cg),
            "accepted_steps": poisson.accepted_steps(
                losses, obj.value_grad(w0)[0])}


def planted(runner, workers, what) -> dict:
    """{name: the faulty objective}, built one at a time by the caller."""
    out = {}
    if "control" in what:
        out["control_bf16"] = lambda: runner.reference_objective(
            workers, rounding=reference.bfloat16_rounding)
    if "faults" in what:
        def no_offsets():
            obj = runner.reference_objective(workers)
            obj.offsets = np.zeros_like(obj.offsets)
            return obj

        out["fault_no_offsets"] = no_offsets
        out["fault_logistic_d2"] = lambda: LogisticCurvature(
            runner.indices, runner.labels, runner.offsets, runner.dim,
            float(runner.cfg["l2"]), workers)
        out["fault_half_batch"] = lambda: runner.reference_objective(
            workers, rows=slice(0, runner.rows // 2), scale=2.0)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", default="criteo-poisson-tron.fit")
    p.add_argument("--seeds", required=True)
    p.add_argument("--what", default="control,faults")
    p.add_argument("--fit", type=int, default=2,
                   help="the starting point: w0 = w0_step * this (a "
                        "window's last fit is its second)")
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", type=int, default=0)
    p.add_argument("--threads", type=int, default=None)
    args = p.parse_args(argv)
    what = set(args.what.split(","))

    cell = harness.load_cell(ROOT, args.workload, bool(args.rehearse))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for seed in [int(s) for s in args.seeds.split(",")]:
            runner = poisson.Runner(cell, seed)
            record = {"workload": args.workload, "seed": seed}
            runner.draw()
            w0 = runner.start_point(args.fit)
            with reference.Workers(args.threads) as workers:
                obj = runner.reference_objective(workers)
                t = time.perf_counter()
                followed = runner.reference_fit(obj, w0)
                record["reference_s"] = time.perf_counter() - t
                record["reference_losses"] = followed[1]
                record["reference_cg"] = followed[3]
                for name, make in planted(runner, workers, what).items():
                    t = time.perf_counter()
                    record[name] = poisson.compare(
                        stand_in(runner, make(), w0), obj, w0, followed,
                        runner.first_steps)
                    record[name + "_s"] = time.perf_counter() - t
            print(json.dumps(record), flush=True)
            out.write(json.dumps(record) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
