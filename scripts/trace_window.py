"""Windows of a benchmark cell under the program's own tracing, for the
builder's table of PERF.md section 5 (not part of a benchmark run).

    python3 scripts/trace_window.py --workload criteo-lr.fit --seed 7 \
        --seconds 20 --tracing profile --out chiprun_out/criteo-lr.fit

Builds the cell's runner as ``benchmark/harness.py`` does (same data, same
set-up fit), then drives ``runner.window`` once for each of ``--tracing``'s
comma-separated modes (``off,photon,off,photon``: one set-up, windows in
turn):

* ``off``: nothing on;
* ``photon``: a photon tracer installed (what ``PHOTON_TRACE=<dir>`` does
  in a driver);
* ``profile``: inside ``obs.trace.profile`` (a driver's ``--profile-dir``),
  after which ``photon-trace kernels`` is run on the trace and written to
  ``<out>.kernels.txt`` / ``.json``, and ``photon-trace gaps`` (the device's
  idle time by host span) to ``<out>.gaps.txt`` / ``.json``;
  ``--keep-trace`` also keeps the ``.xplane.pb`` gzipped (small sizes only:
  see ``--set``).

Prints one JSON line: each window's rate as the harness counts it and the
fit records of its fits (and, for a GLMix cell, the run records of its runs),
the device. ``--set key=value`` overrides numbers of
the cell's configuration (``--set rows_per_chip_log2=12``), for the small
trace recorded under ``tests/data/``.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def one_window(runner, tracing: str, seconds: float, out, keep_trace) -> dict:
    from photon_ml_tpu.obs import trace as obs_trace
    from photon_ml_tpu.obs import xplane
    from photon_ml_tpu.obs.metrics import training_metrics

    trace_dir = tempfile.mkdtemp(prefix="photon_trace_window_")
    try:
        if tracing == "photon":
            obs_trace.start(trace_dir)
            try:
                window = runner.window(seconds)
            finally:
                obs_trace.stop()
        else:
            with obs_trace.profile(trace_dir if tracing == "profile"
                                   else None):
                window = runner.window(seconds)
        pieces = window["pieces"]
        result = {
            "tracing": tracing,
            "train_rows_per_s": window["rows"] * sum(
                q["passes"] for q in pieces)
            / (window["end"] - window["start"]),
            "fit_s": [q["t1"] - q["t0"] for q in pieces],
            "fits": training_metrics().fit_records()[-len(pieces):],
            "runs": training_metrics().run_records()[-len(pieces):],
        }
        if tracing == "profile":
            found = xplane.find_xplane(trace_dir)
            table = xplane.kernel_table(found)
            gaps = xplane.gap_table(xplane.device_ops(found),
                                    xplane.host_spans(found))
            result["attributed_share"] = table["attributed_share"]
            result["busy_s"] = table["busy_s"]
            result["idle_s"] = gaps["idle_s"]
            if out:
                os.makedirs(os.path.dirname(os.path.abspath(out)),
                            exist_ok=True)
                with open(out + ".kernels.txt", "w") as f:
                    f.write(xplane.format_table(table, instructions=8) + "\n")
                with open(out + ".kernels.json", "w") as f:
                    json.dump(table, f)
                with open(out + ".gaps.txt", "w") as f:
                    f.write(xplane.format_gaps(gaps) + "\n")
                with open(out + ".gaps.json", "w") as f:
                    json.dump(gaps, f)
            if keep_trace:
                os.makedirs(os.path.dirname(os.path.abspath(keep_trace)),
                            exist_ok=True)
                with open(found, "rb") as src, \
                        gzip.open(keep_trace, "wb") as dst:
                    shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--tracing", default="profile",
                   help="comma-separated: off, photon, profile")
    p.add_argument("--out", default=None,
                   help="prefix of the kernels table's files")
    p.add_argument("--keep-trace", default=None,
                   help="write the .xplane.pb, gzipped, here")
    p.add_argument("--set", action="append", default=[],
                   metavar="KEY=VALUE", help="override a config number")
    p.add_argument("--rehearse", type=int, choices=[0, 1], default=0)
    args = p.parse_args(argv)

    import jax

    from benchmark import harness

    cell = harness.load_cell(ROOT, args.workload, bool(args.rehearse))
    for item in args.set:
        key, value = item.split("=", 1)
        cell.config[key] = type(cell.config[key])(value)
    harness.configure_cache(jax, ROOT)
    device = harness.look_for_chips(jax, cell.chips, bool(args.rehearse))
    runner = harness.load_module(os.path.join(
        harness.BENCH_DIR, "runners", cell.traffic["runner"] + ".py")
    ).Runner(cell, args.seed)
    runner.setup()

    windows = [one_window(runner, mode, args.seconds, args.out,
                          args.keep_trace)
               for mode in args.tracing.split(",")]
    result = {"workload": args.workload, "seed": args.seed,
              "device": device, "windows": windows}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
