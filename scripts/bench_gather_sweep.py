"""Sweep vector-gather formulations on the chip to pick table_gather's shape.

Variants of ``sum(w[idx])`` at the bench shape (81.8M nnz, d=2^18):
  - slice width L in {8, 16, 32, 128}: table reshaped [d/L, L], row gather
    moves L words per element, one-hot select over L lanes. Narrower rows
    move fewer bytes (L=8 is one 32-byte HBM sector) IF the (1, L) gather
    still vectorizes.
  - chunked (lax.map, bounded intermediate) vs direct (single fused
    expression; tests whether XLA fuses gather->select->reduce without
    materializing the [m, L] intermediate — direct at L=128 is 42 GB if
    it does not fuse, so it runs LAST and an OOM is caught).
  - bf16 table for the winning width (halves gathered bytes; margins
    accumulate in f32).

Salted, scalar-fetch synced (bench.py discipline). Arrays via arguments,
never closures (a closed-over array becomes a program constant).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

REPS = 3


def timed(fn, *args):
    float(fn(jnp.float32(0.0), *args))
    t0 = time.perf_counter()
    for r in range(1, REPS + 1):
        float(fn(jnp.float32(r * 1e-8), *args))
    return (time.perf_counter() - t0) / REPS * 1e3


def main() -> None:
    platform = jax.devices()[0].platform
    small = platform == "cpu"
    n, d, k = ((1 << 14, 1 << 12, 39) if small else (1 << 21, 1 << 18, 39))
    print(f"platform={platform} n={n} d={d} k={k}", flush=True)

    @jax.jit
    def make_data(key):
        k_idx, k_w = jax.random.split(key)
        idx = jax.random.randint(k_idx, (n, k), 0, d, jnp.int32)
        w = jax.random.normal(k_w, (d,), jnp.float32) * 0.5
        return idx, w

    idx, w = jax.block_until_ready(make_data(jax.random.key(0)))
    flat = idx.reshape(-1)
    results = {}

    def rows_select(table2d, ix, L, acc_dtype):
        shift = L.bit_length() - 1
        rows = jnp.take(table2d, jnp.right_shift(ix, shift), axis=0)
        lane = jnp.bitwise_and(ix, L - 1)
        onehot = lane[:, None] == jnp.arange(L, dtype=ix.dtype)[None, :]
        return jnp.sum(jnp.where(onehot, rows.astype(acc_dtype), 0), axis=-1)

    def run_variant(name, L, chunk, dtype):
        table = w.astype(dtype)
        t2 = table.reshape(d // L, L)

        if chunk is None:
            @jax.jit
            def f(salt, t2_, fl):
                return rows_select(t2_ + salt.astype(dtype), fl, L,
                                   jnp.float32).sum()
        else:
            @jax.jit
            def f(salt, t2_, fl):
                t2s = t2_ + salt.astype(dtype)
                c = -(-fl.shape[0] // chunk)
                flp = jnp.pad(fl, (0, c * chunk - fl.shape[0]))
                out = jax.lax.map(
                    lambda ix: rows_select(t2s, ix, L, jnp.float32).sum(),
                    flp.reshape(c, chunk))
                return out.sum()

        try:
            ms = timed(f, t2, flat)
        except Exception as e:  # noqa: BLE001 - OOM etc is a data point
            msg = str(e).split("\n")[0][:120]
            print(f"{name}: FAILED {msg}", flush=True)
            results[name] = None
            return
        gb = flat.size * (L * jnp.dtype(dtype).itemsize + 4) / 1e9
        print(f"{name}: {ms:.1f} ms  (~{gb / (ms / 1e3):.0f} GB/s "
              "at gather+idx traffic)", flush=True)
        results[name] = ms

    # serial baseline for reference
    @jax.jit
    def serial(salt, w_, fl):
        return jnp.sum((w_ + salt)[fl])

    results["serial"] = timed(serial, w, flat)
    print(f"serial: {results['serial']:.1f} ms", flush=True)

    for L in (8, 16, 32, 128):
        run_variant(f"w{L}_chunk18", L, 1 << 18, jnp.float32)
    run_variant("w8_chunk20", 8, 1 << 20, jnp.float32)
    run_variant("w32_chunk20", 32, 1 << 20, jnp.float32)
    run_variant("w128_chunk20", 128, 1 << 20, jnp.float32)
    # direct last: OOM risk if unfused
    run_variant("w8_direct", 8, None, jnp.float32)
    run_variant("w128_direct", 128, None, jnp.float32)
    # bf16 table at two widths
    run_variant("w8_chunk18_bf16", 8, 1 << 18, jnp.bfloat16)
    run_variant("w128_chunk18_bf16", 128, 1 << 18, jnp.bfloat16)

    print(json.dumps({"metric": "gather_sweep_ms", "platform": platform,
                      "results": results}), flush=True)


if __name__ == "__main__":
    main()
