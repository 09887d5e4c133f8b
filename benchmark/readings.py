"""The readings the limits of ``correct`` are set from (not part of a run).

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,3 \
        --what program,control,faults --out <file.jsonl>

For each seed, in one process, at the cell's own size:

* ``program``: set-up's fit (the window's own call and feed) against the
  reference: the lower readings;
* ``control``: the reference computed in bfloat16 (every vector the
  objective reads or hands back rounded to it) put in the program's place:
  the upper readings;
* ``faults``: the reference with a fault planted, in the program's place:
  half of the batch left out and the mean taken over the rest; on a
  several-chip mesh, the exchange left out (one chip's rows alone). A step
  that returns its state unchanged reads 1 by the measure used and needs no
  run.

Needs the chip for ``program``; ``control`` and ``faults`` are host work and
run wherever the data fits.
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


def stand_in(runner, obj, w0):
    """What the program would have reported had it computed like ``obj``:
    the whole fit followed, its end as the fit's result."""
    w, losses, gnorms = runner.reference_fit(obj, w0)
    return {"w": w, "value": losses[-1], "grad_norm": gnorms[-1],
            "loss_history": np.asarray(losses),
            "grad_norm_history": np.asarray(gnorms)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--what", default="program,control,faults")
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", type=int, default=0)
    args = p.parse_args(argv)
    what = set(args.what.split(","))

    from benchmark import harness, reference
    from benchmark.runners import glm_fit

    cell = harness.load_cell(ROOT, args.workload, bool(args.rehearse))
    if "program" in what:
        import jax

        harness.configure_cache(jax, ROOT)
        print(harness.look_for_chips(jax, cell.chips, bool(args.rehearse)),
              flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for seed in [int(s) for s in args.seeds.split(",")]:
            runner = glm_fit.Runner(cell, seed)
            record = {"workload": args.workload, "seed": seed}
            t = time.perf_counter()
            if "program" in what:
                runner.setup()
                pieces = [runner.fit(j) for j in (1, 2)]
                record["fit_s"] = [p["t1"] - p["t0"] for p in pieces]
                record["phases"] = dict(runner.phases)
                runner.release()
                got, i = runner.fetched, runner.fetched["i"]
            else:
                from benchmark import data

                runner.indices, runner.labels = data.criteo_rows(
                    runner.rows, runner.dim, runner.k,
                    int(cell.config["data_seed"]), seed)
                got, i = None, 0
            record["setup_s"] = time.perf_counter() - t
            w0 = runner.start_point(i)
            with reference.Workers() as workers:
                obj = runner.reference_objective(workers)
                t = time.perf_counter()
                followed = runner.reference_fit(obj, w0)
                record["reference_s"] = time.perf_counter() - t
                if got is not None:
                    record["program"] = glm_fit.compare(
                        got, obj, w0, followed, runner.first_steps)
                planted = {}
                if "control" in what:
                    planted["control_bf16"] = dict(
                        rounding=reference.bfloat16_rounding)
                if "faults" in what:
                    n = runner.rows
                    planted["fault_half_batch"] = dict(
                        rows=slice(0, n // 2), scale=2.0)
                    if cell.chips > 1:
                        planted["fault_no_exchange"] = dict(
                            rows=slice(0, n // cell.chips))
                for name, kw in planted.items():
                    t = time.perf_counter()
                    bad = runner.reference_objective(workers, **kw)
                    record[name] = glm_fit.compare(
                        stand_in(runner, bad, w0), obj, w0, followed,
                        runner.first_steps)
                    record[name + "_s"] = time.perf_counter() - t
            print(json.dumps(record), flush=True)
            out.write(json.dumps(record) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
