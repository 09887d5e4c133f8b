"""Streamed (larger-than-HBM) fit throughput on the current backend
(VERDICT r2 #3 / r3 #5: the north star only runs in this mode and it has
no usable hardware measurement at bench scale).

Builds a Criteo-shaped dataset in HOST RAM as fixed-shape chunks, runs the
streamed L-BFGS fit, and reports end-to-end examples/sec INCLUDING
host->device transfer, next to the in-HBM fit on the same data for the
streaming-overhead ratio.

Hardened against a run that wedges (VERDICT r3 weak #4):

- **Per-iteration progress + checkpoint.** Every completed optimizer
  iteration logs a timestamped line and writes ``--checkpoint`` (current
  w + iterations done + elapsed), so a wedge loses one iteration of
  evidence, not the run.
- **Stall watchdog + resumable exit.** If no iteration completes within
  ``--stall-timeout`` the harness emits a PARTIAL json record with
  everything measured so far and exits rc=3. The caller (the session
  script) halves ``--chunk-rows`` and re-invokes with ``--resume``: the
  fit warm-starts from the checkpointed w and runs only the remaining
  iterations (noted in the record — a resumed headline is labeled).
- **Transfer budget.** The per-transfer cap stays sharp (one oversized
  upload is the wedge/crash vector — docs/PERF.md); the by-design bulk
  total of a streamed fit is declared via an explicit waiver.

Usage: python scripts/bench_streaming.py [--rows-log2 N] [--chunk-rows N]
       [--resume] [--stall-timeout S]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows-log2", type=int, default=None)
    ap.add_argument("--chunk-rows", type=int, default=None)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--optimizer", default="lbfgs",
                    help="lbfgs (margin-space trials, default) or "
                         "lbfgs_blackbox (full pass per trial)")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="hard watchdog on the whole harness")
    ap.add_argument("--stall-timeout", type=float, default=300.0,
                    help="no-iteration-progress window before the PARTIAL "
                         "record + rc=3 exit")
    ap.add_argument("--checkpoint", default="/tmp/bench_streaming_ckpt.npz")
    ap.add_argument("--resume", action="store_true",
                    help="warm-start from --checkpoint (after a stall "
                         "exit; typically with a halved --chunk-rows)")
    ap.add_argument("--skip-in-hbm", action="store_true")
    ap.add_argument("--dim-log2", type=int, default=None)
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="shard the streamed fit over a data-parallel mesh "
                         "of this width (VERDICT r3 contingency: the "
                         "8-virtual-device streamed bench-shape record)")
    args = ap.parse_args()

    state = {"iters_done": 0, "elapsed": 0.0, "last_progress": time.time(),
             "phase": "startup", "resumed_from": 0, "headline_done": False,
             "stall_armed": True}

    def emit(metric, value, unit, rc=None):
        print(json.dumps({"metric": metric, "value": round(value, 1),
                          "unit": unit}), flush=True)
        if rc is not None:
            os._exit(rc)

    def partial_unit(tag):
        return (f"{tag} ({state['phase']}): {state['iters_done']} iters "
                f"(from {state['resumed_from']}) in {state['elapsed']:.1f}s"
                f" — resume with --resume and halved --chunk-rows")

    def fire(tag):
        if state["headline_done"]:
            # the measurement is already out; don't let a wedged in-HBM
            # comparison turn a successful run into a retry loop
            print(f"{tag} during {state['phase']} (headline already "
                  "emitted) — exiting clean", file=sys.stderr, flush=True)
            os._exit(0)
        done = state["iters_done"] - state["resumed_from"]
        v = (N_ROWS[0] * done / state["elapsed"]) if done and state["elapsed"] else 0.0
        emit("streaming_examples_per_sec", v, partial_unit(tag), rc=3)

    t = threading.Timer(args.timeout,
                        lambda: fire(f"TIMEOUT after {args.timeout:.0f}s"))
    t.daemon = True
    t.start()

    def stall_watch():
        while True:
            time.sleep(5.0)
            if (state["stall_armed"]
                    and time.time() - state["last_progress"]
                    > args.stall_timeout):
                fire(f"STALL >{args.stall_timeout:.0f}s")

    N_ROWS = [0]  # filled once shapes are known; watchdogs read it

    import jax

    if args.mesh_devices > 1:
        try:
            jax.config.update("jax_num_cpu_devices", args.mesh_devices)
        except RuntimeError:
            pass  # backend already up; the assert below decides
    import jax.numpy as jnp

    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.parallel.data_parallel import fit_distributed
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.parallel.streaming import HostChunk, fit_streaming
    from photon_ml_tpu.types import LabeledBatch, SparseFeatures
    from photon_ml_tpu.utils import transfer_budget as tb

    # liveness: every sanctioned chunk upload refreshes the stall window.
    # The margin-ladder line search streams whole passes without firing the
    # optimizer progress callback (only ACCEPTED iterations do), so a
    # legitimately long ladder/history-reset retry must not be killed as a
    # stall (ADVICE r4) — per-pass transfer activity is the honest signal.
    tb.set_activity_hook(
        lambda: state.__setitem__("last_progress", time.time()))

    platform = jax.devices()[0].platform
    mesh = None
    if args.mesh_devices > 1:
        assert len(jax.devices()) >= args.mesh_devices, (
            f"need {args.mesh_devices} devices, have {len(jax.devices())}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count")
        mesh = make_mesh({"data": args.mesh_devices})
    rows_log2 = args.rows_log2 or (19 if platform != "cpu" else 14)
    n, k = 1 << rows_log2, 39
    N_ROWS[0] = n
    dim = 1 << (args.dim_log2 or (18 if platform != "cpu" else 13))
    chunk_rows = args.chunk_rows or (1 << 14 if platform != "cpu"
                                     else 1 << 12)
    iters = args.iters

    rng = np.random.default_rng(0)
    indices = rng.integers(0, dim, (n, k)).astype(np.int32)
    labels = rng.integers(0, 2, n).astype(np.float32)
    print(f"host dataset: n={n} k={k} dim={dim} "
          f"({indices.nbytes/1e9:.2f} GB idx) chunk_rows={chunk_rows}",
          file=sys.stderr, flush=True)

    # implicit-ones layout (values=None): Criteo-style one-hot rows, half
    # the host->device bytes per chunk on the transfer-bound streamed path
    chunks = []
    zeros = np.zeros(chunk_rows, np.float32)
    ones = np.ones(chunk_rows, np.float32)
    for s in range(0, n, chunk_rows):
        e = s + chunk_rows
        chunks.append(HostChunk(indices[s:e], None, labels[s:e],
                                zeros, ones))

    # transfer budget: keep the per-transfer cap sharp (a single bulk
    # upload is what crashes the worker); the streamed total is by-design
    # bulk, so declare it. The per-transfer unit is ONE FIELD ARRAY
    # (streaming's _chunk_to_device/_put upload each chunk field
    # separately), so the cap pre-check sizes the largest field of the
    # ACTUAL chunks — a values-carrying layout is sized correctly instead
    # of dying mid-fit on the budget raise (ADVICE r4). Per-pass bytes ~=
    # indices + values + labels/offsets/weights + margin-trial vectors;
    # x(iters+2) passes x2 headroom.
    chunk_mb = max(
        a.nbytes
        for c in chunks
        for a in (c.indices, c.values, c.labels, c.offsets, c.weights)
        if a is not None) / 1e6
    values_bytes = sum(c.values.nbytes for c in chunks
                       if c.values is not None)
    per_pass_mb = (indices.nbytes + values_bytes + 3 * 4 * n + 2 * 4 * n) / 1e6
    need_mb = per_pass_mb * (iters + 2) * 6
    if chunk_mb > 64.0:
        # the per-transfer cap is never relaxed. Refuse up front rather
        # than dying
        # mid-fit on the budget raise.
        print(f"error: chunk_rows={chunk_rows} is a {chunk_mb:.0f} MB "
              "upload per chunk field, above the 64MB "
              "per-transfer cap — use a smaller --chunk-rows",
              file=sys.stderr, flush=True)
        sys.exit(2)
    if tb.get_budget() is not None:
        tb.waive(need_mb, reason="streamed fit moves the dataset per pass "
                                 "by design; per-transfer cap unchanged")
    else:
        tb.set_budget(total_mb=need_mb, single_mb=64.0,
                      label="bench_streaming")

    # r5: the >=64-chunk refusal is GONE. Root cause (minimal repro in
    # scripts/repro_cpu_collective_deadlock.py): async-dispatched sharded
    # chunk programs each carried a GSPMD all-reduce, and XLA:CPU's
    # in-process rendezvous loses a participant once ~64 collective
    # executions queue unsynced. The per-chunk kernels are now
    # collective-free (shard_map per-device partials, one reduction per
    # pass — parallel/streaming._shard_map_chunk), so chunk count is
    # unbounded on every backend.

    obj = make_objective("logistic")
    w0 = jnp.zeros((dim,), jnp.float32)
    if args.resume and os.path.exists(args.checkpoint):
        ck = np.load(args.checkpoint)
        w0 = jnp.asarray(ck["w"])
        state["resumed_from"] = int(ck["iters_done"])
        iters = max(args.iters - state["resumed_from"], 1)
        print(f"resuming from iteration {state['resumed_from']} "
              f"({args.checkpoint}); {iters} to go", file=sys.stderr,
              flush=True)
    cfg = OptimizerConfig(max_iters=iters, tolerance=0.0)

    t_start = [time.time()]

    def on_progress(it, w):
        now = time.time()
        state["iters_done"] = state["resumed_from"] + it + 1
        state["elapsed"] = now - t_start[0]
        state["last_progress"] = now
        # atomic write: a kill mid-savez must not leave a truncated
        # checkpoint that poisons every --resume attempt after it
        tmp_ck = args.checkpoint + ".tmp.npz"
        np.savez(tmp_ck, w=np.asarray(w), iters_done=state["iters_done"])
        os.replace(tmp_ck, args.checkpoint)
        print(f"  iter {state['iters_done']}/{args.iters} "
              f"t={state['elapsed']:.1f}s", file=sys.stderr, flush=True)

    def stream_fit(salt, run_cfg, callback=None):
        # salted w0: warm-up and timed run must be distinct computations
        res = fit_streaming(obj, chunks, dim, w0 + jnp.float32(salt) * 1e-8,
                            l2=1.0, config=run_cfg, optimizer=args.optimizer,
                            mesh=mesh, progress_callback=callback)
        int(res.iterations)  # scalar fetch: true end-to-end sync
        return res

    state["phase"] = "compile"
    # one-iteration warm-up: compiles every kernel without paying a full
    # extra fit at big shapes (the runner cache keeps them for the timed run)
    stream_fit(1, OptimizerConfig(max_iters=1, tolerance=0.0))

    state["phase"] = "timed"
    state["last_progress"] = time.time()
    # stall enforcement starts only now: a slow compile in the warm-up
    # is normal (minutes), a timed iteration going silent for
    # --stall-timeout is not
    threading.Thread(target=stall_watch, daemon=True).start()
    t_start[0] = time.time()
    res = stream_fit(2, cfg, callback=on_progress)
    dt_stream = time.time() - t_start[0]
    done = max(int(res.iterations), 1)
    v_stream = n * done / dt_stream
    resumed = (f", resumed@{state['resumed_from']}"
               if state["resumed_from"] else "")
    state["headline_done"] = True
    mesh_note = (f", data-mesh={args.mesh_devices}"
                 if args.mesh_devices > 1 else "")
    emit("streaming_examples_per_sec", v_stream,
         f"example-passes/sec end-to-end incl transfer ({platform},"
         f" n={n}, d={dim}, k={k}, chunk_rows={chunk_rows},"
         f" iters={done}{resumed}{mesh_note}, optimizer={args.optimizer})")

    if args.skip_in_hbm:
        return
    # in-HBM comparison on the same data (may OOM at big shapes; guarded).
    # Upload chunk-by-chunk and concatenate ON DEVICE: one bulk
    # jnp.asarray(indices) of hundreds of MB is the transfer shape the
    # per-transfer cap refuses.
    state["phase"] = "in-hbm"
    # disarm the stall watchdog here: mem_fit(1) is a fresh jit compile
    # (minutes) with no progress
    # callbacks to feed it, and a false stall would silently lose the
    # streaming/in-HBM ratio. The hard --timeout still bounds the process.
    state["stall_armed"] = False
    try:
        tb.waive(2 * indices.nbytes / 1e6 + 64,
                 reason="in-HBM comparison uploads the dataset once, "
                        "chunkwise")
        dev_idx = jnp.concatenate(
            [tb.device_put(c.indices, what="in-hbm chunk") for c in chunks],
            axis=0)
        batch = LabeledBatch(
            SparseFeatures(dev_idx, None, dim=dim),
            jnp.asarray(labels), jnp.zeros((n,), jnp.float32),
            jnp.ones((n,), jnp.float32))
        hbm_mesh = mesh if mesh is not None else make_mesh()

        def mem_fit(salt):
            r = fit_distributed(obj, batch, hbm_mesh,
                                w0 + jnp.float32(salt) * 1e-8, l2=1.0,
                                config=cfg)
            int(r.iterations)  # scalar fetch: true sync
            return r

        r = mem_fit(1)
        t0 = time.perf_counter()
        r = mem_fit(2)
        dt_mem = time.perf_counter() - t0
        v_mem = n * max(int(r.iterations), 1) / dt_mem
        emit("in_hbm_examples_per_sec_same_data", v_mem,
             f"example-passes/sec ({platform}); streaming/in-HBM ="
             f" {v_stream / v_mem:.3f}")
    except Exception as e:
        print(f"in-HBM comparison skipped: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
