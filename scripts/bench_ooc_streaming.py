"""Out-of-core streamed fit over an ON-DISK Avro dataset (VERDICT r4 #2).

The north-star configuration: no host-RAM-resident dataset at all. The
harness writes (once; ``--reuse`` keeps it) a Criteo-shaped Avro dataset to
disk, then runs ``fit_streaming`` over an :class:`AvroChunkSource` — block
waves decode on a background thread through the native C++ decoder into a
bounded queue, so peak host residency is ``(prefetch + 2)`` chunks
regardless of dataset size.

Reported (one JSON line each):
- ``ooc_streaming_examples_per_sec`` — end-to-end fit throughput including
  per-pass disk re-decode + host->device transfer;
- decode-only pass throughput and the in-RAM streamed fit on the same data
  (when it fits), attributing the out-of-core overhead;
- peak-RSS delta and the chunk-residency bound as the memory evidence.

Usage: python scripts/bench_ooc_streaming.py [--rows N] [--chunk-rows N]
       [--iters N] [--reuse] [--data DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--k", type=int, default=39)
    ap.add_argument("--dim-log2", type=int, default=16)
    ap.add_argument("--chunk-rows", type=int, default=1 << 14)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--data", default="/tmp/ooc_bench_data")
    ap.add_argument("--reuse", action="store_true",
                    help="reuse an existing dataset file")
    ap.add_argument("--skip-in-ram", action="store_true")
    ap.add_argument("--timeout", type=float, default=1800.0)
    args = ap.parse_args()

    import threading

    def die():
        print(json.dumps({
            "metric": "ooc_streaming_examples_per_sec", "value": 0.0,
            "unit": f"TIMEOUT after {args.timeout:.0f}s"}), flush=True)
        os._exit(2)

    t = threading.Timer(args.timeout, die)
    t.daemon = True
    t.start()

    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.hashing import HashingIndexMap
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
    from photon_ml_tpu.io.stream_source import AvroChunkSource
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.parallel.streaming import (
        fit_streaming,
        make_host_chunks,
    )
    from photon_ml_tpu.game.data import HostSparse

    n, k, dim = args.rows, args.k, 1 << args.dim_log2
    os.makedirs(args.data, exist_ok=True)
    path = os.path.join(args.data, f"criteo_shaped_n{n}_k{k}.avro")

    if not (args.reuse and os.path.exists(path)):
        # Criteo-shaped categorical rows: k hashed features per row, value
        # 1.0. Written through the spec-conformant codec (null codec: the
        # write is fixture setup, not the measurement).
        t0 = time.time()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 1 << 31, size=(n, k))
        labels = rng.integers(0, 2, n)

        def records():
            for i in range(n):
                yield {
                    "uid": str(i),
                    "response": float(labels[i]),
                    "offset": None, "weight": None,
                    "features": [
                        {"name": f"c{j}", "term": str(ids[i, j]),
                         "value": 1.0} for j in range(k)],
                    "metadataMap": {},
                }

        # tmp+rename: a killed multi-minute write must never leave a
        # truncated file that a later --reuse silently benches against
        tmp = f"{path}.tmp-{os.getpid()}"
        write_avro_file(tmp, records(), TRAINING_EXAMPLE_SCHEMA,
                        codec="null")
        os.replace(tmp, path)
        print(f"wrote {path} ({os.path.getsize(path)/1e6:.1f} MB) "
              f"in {time.time()-t0:.1f}s", file=sys.stderr, flush=True)

    file_mb = os.path.getsize(path) / 1e6
    imap = HashingIndexMap(dim, add_intercept=True)
    rss0 = _rss_mb()

    # transfer budget (same policy as bench_streaming): per-transfer cap
    # stays sharp; the by-design per-pass bulk total is declared up front
    from photon_ml_tpu.utils import transfer_budget as tb

    field_mb = args.chunk_rows * (k + 1) * 4 / 1e6
    if field_mb > 64.0:
        print(f"error: chunk_rows={args.chunk_rows} is a {field_mb:.0f} MB "
              "upload per chunk field, above the 64MB per-transfer cap",
              file=sys.stderr, flush=True)
        sys.exit(2)
    per_pass_mb = n * ((k + 1) * 8 + 12) / 1e6
    # generous by-design-bulk total: warm-up + timed + in-RAM comparison
    # fits each pay ~2 sparse passes/iter plus margin-ladder streams; the
    # sharp protection is the per-transfer cap, not this total
    need_mb = per_pass_mb * (args.iters + 4) * 10
    if tb.get_budget() is not None:
        tb.waive(need_mb, reason="ooc streamed fit re-uploads the dataset "
                                 "per pass by design")
    else:
        tb.set_budget(total_mb=need_mb, single_mb=64.0, label="bench_ooc")

    src = AvroChunkSource(path, imap, chunk_rows=args.chunk_rows,
                          pad_nnz=k + 1, prefetch=args.prefetch)
    if src.total_rows != n:
        print(f"error: {path} holds {src.total_rows} rows, expected {n} "
              "(stale/partial --reuse dataset?); delete it and rerun",
              file=sys.stderr, flush=True)
        sys.exit(2)
    chunk_mb = args.chunk_rows * (k + 1) * 8 / 1e6  # idx i32 + val f32
    print(f"source: {len(src)} chunks x {args.chunk_rows} rows "
          f"({chunk_mb:.1f} MB/chunk, residency bound "
          f"{(args.prefetch + 2) * chunk_mb:.1f} MB vs {file_mb:.1f} MB "
          "on disk)", file=sys.stderr, flush=True)

    # decode-only pass: attributes the ingestion cost inside the fit number
    t0 = time.time()
    n_c = sum(1 for _ in src)
    dt_decode = time.time() - t0
    assert n_c == len(src)
    print(f"decode-only pass: {dt_decode:.2f}s "
          f"({n / dt_decode:.0f} rows/s, "
          f"{file_mb / dt_decode:.1f} MB/s)", file=sys.stderr, flush=True)

    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=args.iters, tolerance=0.0)
    # compile warm-up (1 iter), then the timed fit (salted start)
    fit_streaming(obj, src, src.dim,
                  w0=jnp.zeros((src.dim,), jnp.float32),
                  l2=1.0, config=OptimizerConfig(max_iters=1, tolerance=0.0))
    t0 = time.time()
    res = fit_streaming(obj, src, src.dim,
                        w0=jnp.full((src.dim,), 1e-8, jnp.float32),
                        l2=1.0, config=cfg)
    int(res.iterations)  # scalar fetch: true sync
    dt = time.time() - t0
    done = max(int(res.iterations), 1)
    v = n * done / dt
    rss_delta = _rss_mb() - rss0
    platform = jax.devices()[0].platform
    print(json.dumps({
        "metric": "ooc_streaming_examples_per_sec", "value": round(v, 1),
        "unit": (f"example-passes/sec end-to-end incl per-pass disk decode "
                 f"({platform}, n={n}, d={dim}, k={k}, "
                 f"chunk_rows={args.chunk_rows}, iters={done}, "
                 f"passes={src.passes}, decode-only "
                 f"{file_mb / dt_decode:.1f} MB/s, peak-RSS delta "
                 f"{rss_delta:.0f} MB vs {file_mb:.0f} MB dataset)"),
    }), flush=True)

    if args.skip_in_ram:
        return
    # same fit with the dataset held in RAM: the out-of-core overhead ratio
    feats_i = np.empty((n, k + 1), np.int32)
    feats_v = np.ones((n, k + 1), np.float32)
    labels_a = np.empty(n, np.float32)
    r = 0
    for c in src:
        rows = min(args.chunk_rows, n - r)
        feats_i[r:r + rows] = c.indices[:rows]
        feats_v[r:r + rows] = c.values[:rows]
        labels_a[r:r + rows] = c.labels[:rows]
        r += rows
    chunks, _ = make_host_chunks(
        HostSparse(feats_i, feats_v, src.dim), labels_a,
        chunk_rows=args.chunk_rows)
    fit_streaming(obj, chunks, src.dim,
                  w0=jnp.zeros((src.dim,), jnp.float32), l2=1.0,
                  config=OptimizerConfig(max_iters=1, tolerance=0.0))
    t0 = time.time()
    res2 = fit_streaming(obj, chunks, src.dim,
                         w0=jnp.full((src.dim,), 1e-8, jnp.float32),
                         l2=1.0, config=cfg)
    int(res2.iterations)
    dt_ram = time.time() - t0
    v_ram = n * max(int(res2.iterations), 1) / dt_ram
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(res2.w),
                               rtol=2e-4, atol=1e-6)
    print(json.dumps({
        "metric": "in_ram_streaming_examples_per_sec_same_data",
        "value": round(v_ram, 1),
        "unit": (f"example-passes/sec ({platform}); ooc/in-RAM = "
                 f"{v / v_ram:.3f}; solutions match"),
    }), flush=True)


if __name__ == "__main__":
    main()
