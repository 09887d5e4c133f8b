"""The readings ``criteo-enet.fit``'s limits are set from (not part of a
run): ``benchmark/readings.py`` for the elastic-net runner, whose faults are
its own.

    python3 benchmark/readings_enet.py --workload criteo-enet.fit \
        --seeds 1,2,3 --what program,control,faults --out <file.jsonl>

For each seed, in one process, at the cell's own size:

* ``program``: set-up's fit and two more (the window's own call and feed),
  the last against the reference: the lower readings. The cell's own runs
  print the same numbers (``compared``) of their windows' last fits;
* ``control``: the reference with every vector the objective reads or hands
  back rounded to bfloat16, in the program's place;
* ``faults``, planted in the reference, in the program's place: half of the
  batch left out and the rest counted twice; the L1 term left out of the
  value the line search compares (and so of the value reported); the trial
  points left unprojected.

Needs the chip for ``program``; the rest is host work.
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


def stand_in(runner, obj, w0, **faults):
    """What the program would have reported had it computed like ``obj``
    (and searched with ``faults``): the whole fit followed, its end as the
    fit's result."""
    w, values, pgnorms, _ = runner.reference_fit(obj, w0, **faults)
    return {"w": w, "value": values[-1], "grad_norm": pgnorms[-1],
            "loss_history": np.asarray(values),
            "grad_norm_history": np.asarray(pgnorms)}


def planted(runner, what) -> dict:
    """{name: (the objective's keywords, the search's keywords)}."""
    from benchmark import reference

    out = {}
    if "control" in what:
        out["control_bf16"] = (
            dict(rounding=reference.bfloat16_rounding), {})
    if "faults" in what:
        out["fault_half_batch"] = (
            dict(rows=slice(0, runner.rows // 2), scale=2.0), {})
        out["fault_no_l1_in_search"] = ({}, dict(l1_in_search=False))
        out["fault_no_projection"] = ({}, dict(project=False))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", default="criteo-enet.fit")
    p.add_argument("--seeds", required=True)
    p.add_argument("--what", default="program,control,faults")
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", type=int, default=0)
    p.add_argument("--threads", type=int, default=None)
    args = p.parse_args(argv)
    what = set(args.what.split(","))

    from benchmark import data, harness, reference

    cell = harness.load_cell(ROOT, args.workload, bool(args.rehearse))
    enet = harness.load_module(os.path.join(
        harness.BENCH_DIR, "runners", cell.traffic["runner"] + ".py"))
    if "program" in what:
        import jax

        harness.configure_cache(jax, ROOT)
        print(harness.look_for_chips(jax, cell.chips, bool(args.rehearse)),
              flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for seed in [int(s) for s in args.seeds.split(",")]:
            runner = enet.Runner(cell, seed)
            record = {"workload": args.workload, "seed": seed}
            got, i = None, 1
            if "program" in what:
                runner.setup()
                pieces = [runner.fit(j) for j in (1, 2)]
                record["fit_s"] = [q["t1"] - q["t0"] for q in pieces]
                record["pieces"] = [
                    {k: q[k] for k in ("passes", "trials", "nonzeros")}
                    for q in pieces]
                runner.release()
                got, i = runner.fetched, runner.fetched["i"]
            else:
                runner.indices, runner.labels = data.criteo_rows(
                    runner.rows, runner.dim, runner.k,
                    int(cell.config["data_seed"]), seed)
            w0 = runner.start_point(i)
            with reference.Workers(args.threads) as workers:
                obj = runner.reference_objective(workers)
                t = time.perf_counter()
                followed = runner.reference_fit(obj, w0)
                record["reference_s"] = time.perf_counter() - t
                record["reference_trials"] = followed[3]
                record["reference_nonzeros"] = int(
                    np.count_nonzero(followed[0]))
                if got is not None:
                    record["program"] = enet.compare(
                        got, obj, w0, followed, runner.first_steps)
                for name, (obj_kw, search_kw) in planted(runner,
                                                         what).items():
                    t = time.perf_counter()
                    bad = (runner.reference_objective(workers, **obj_kw)
                           if obj_kw else obj)
                    record[name] = enet.compare(
                        stand_in(runner, bad, w0, **search_kw), obj, w0,
                        followed, runner.first_steps)
                    record[name + "_s"] = time.perf_counter() - t
            print(json.dumps(record), flush=True)
            out.write(json.dumps(record) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
