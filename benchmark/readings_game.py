"""The readings the limits of the GLMix cells' ``correct`` are set from
(not part of a run; ``benchmark/readings.py`` is the same for the
fixed-effect cells).

    python3 benchmark/readings_game.py --workload <cell> --seeds 1,2,3 \
        --what program,control,faults --out <file.jsonl>

For each seed, in one process, at the cell's own size:

* ``program``: set-up's run and two more pieces (the window's own call)
  against the reference: the lower readings;
* ``control``: the reference computed in bfloat16 (every vector a solve
  reads or hands back rounded to it) put in the program's place;
* ``faults``: the reference with a fault planted
  (``reference_game.FAULTS``), in the program's place: the upper readings.

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


def stand_in(ref, sweeps: int, caps: dict, **kw) -> dict:
    """What the program would have reported had it computed like ``ref``
    (with ``fault`` planted): its model and its records."""
    model, records = ref.follow(sweeps, caps, **kw)
    return {"model": model,
            "fixed_loss": [r["fixed_loss"] for r in records],
            "train_loss": [r["data_loss"] for r in records]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--what", default="program,control,faults")
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", type=int, default=0)
    args = p.parse_args(argv)
    what = set(args.what.split(","))

    from benchmark import data_game, harness, reference, reference_game
    from benchmark.runners import game_cd

    cell = harness.load_cell(ROOT, args.workload, bool(args.rehearse))
    if "program" in what:
        import jax

        harness.configure_cache(jax, ROOT)
        print(harness.look_for_chips(jax, cell.chips, bool(args.rehearse)),
              flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for seed in [int(s) for s in args.seeds.split(",")]:
            runner = game_cd.Runner(cell, seed)
            record = {"workload": args.workload, "seed": seed}
            t = time.perf_counter()
            got, j = None, 0
            if "program" in what:
                runner.setup()
                pieces = [runner.piece(i) for i in (1, 2)]
                record["piece_s"] = [q["t1"] - q["t0"] for q in pieces]
                record["phases"] = dict(runner.phases)
                runner.release()
                got, j = runner.fetched, runner.fetched["j"]
            else:
                runner.rows = data_game.glmix_rows(cell.config, seed)
            record["setup_s"] = time.perf_counter() - t
            with reference.Workers() as workers:
                t = time.perf_counter()
                ref = runner.reference(workers, j)
                record["reference_build_s"] = time.perf_counter() - t
                t = time.perf_counter()
                followed = ref.follow(runner.sweeps, runner.caps)
                record["reference_s"] = time.perf_counter() - t
                if got is not None:
                    record["program"] = game_cd.compare(got, ref, followed)
                planted = {}
                if "control" in what:
                    planted["control_bf16"] = (runner.reference(
                        workers, j, rounding=reference.bfloat16_rounding),
                        {})
                if "faults" in what:
                    for fault in reference_game.FAULTS:
                        planted["fault_" + fault] = (ref, {"fault": fault})
                for name, (bad, kw) in planted.items():
                    t = time.perf_counter()
                    record[name] = game_cd.compare(
                        stand_in(bad, runner.sweeps, runner.caps, **kw),
                        ref, followed)
                    record[name + "_s"] = time.perf_counter() - t
            print(json.dumps(record), flush=True)
            out.write(json.dumps(record) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
