"""Minimal repro for the XLA-CPU in-process collective deadlock
(docs/PERF.md round-4 contingency; VERDICT r4 next-step #4).

The deadlock needs collective executions QUEUED UNSYNCED: a jitted
program containing a GSPMD all-reduce, dispatched asynchronously in a
dataflow chain with no host sync until the end (exactly how the streamed
loops dispatch chunks). On this box ~64 queued collective executions lose
a rendezvous participant (7 of 8 arrive) and the runtime SIGABRTs at the
terminate timeout. The SAME program host-synced after every execution
runs indefinitely — demonstrated by ``--sync``.

Modes:
- ``async`` (default): dispatch-all-then-sync chain of all-reduce
  programs — REPRODUCES the deadlock (expect SIGABRT / watchdog rc=3).
- ``sync``: same program, ``float()`` fetch per execution — runs clean,
  isolating async queue depth (not collective count) as the trigger.
- ``shard_acc``: the fix shape — collective-free per-device accumulation
  (shard_map partials) chained async, ONE reduce at the end — runs clean
  at any chain length. This is what parallel/streaming.py now does.

Run: python scripts/repro_cpu_collective_deadlock.py [--mode async]
     [--n 256] [--devices 8]
Exit 0 = completed; rc=3 = watchdog-detected stall; SIGABRT(134) = the
runtime's own rendezvous terminate — both of the latter reproduce the bug.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256,
                    help="chained executions of the sharded program")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--stall-timeout", type=float, default=90.0)
    ap.add_argument("--mode", default="async",
                    choices=["async", "sync", "shard_acc"])
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert len(jax.devices()) >= args.devices
    mesh = Mesh(jax.devices()[: args.devices], ("data",))
    sh = NamedSharding(mesh, P("data"))

    start = time.time()

    def watchdog():
        time.sleep(args.stall_timeout)
        print(f"STALL: no completion after {args.stall_timeout:.0f}s — "
              "deadlock reproduced", flush=True)
        os._exit(3)

    threading.Thread(target=watchdog, daemon=True).start()

    x = jax.device_put(jnp.ones((args.rows, args.dim), jnp.float32), sh)

    if args.mode in ("async", "sync"):
        @jax.jit
        def step(xs, acc):
            # row-sum of a row-sharded array -> replicated [dim]: GSPMD
            # inserts an all-reduce, like the pre-r5 streamed chunk_fg
            return acc + jnp.sum(xs, axis=0)

        acc = jnp.zeros((args.dim,), jnp.float32)
        for i in range(args.n):
            acc = step(x, acc)
            if args.mode == "sync":
                float(acc[0])  # host sync per execution: runs clean
        total = float(acc[0])  # async: first sync happens HERE
        print(f"{args.mode} done: {args.n} chained all-reduce executions "
              f"in {time.time() - start:.1f}s (sum[0]={total:.0f})",
              flush=True)
        return

    # shard_acc: the collective-free fix shape
    @jax.jit
    @lambda f: jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                             out_specs=P("data"), check_vma=False)
    def acc_step(xs, acc):
        return acc + jnp.sum(xs, axis=0, keepdims=True)

    @jax.jit
    def reduce_acc(a):
        return jnp.sum(a, axis=0)

    acc = jax.device_put(
        jnp.zeros((args.devices, args.dim), jnp.float32), sh)
    for i in range(args.n):
        acc = acc_step(x, acc)  # chained async, NO collective inside
    out = reduce_acc(acc)       # the pass's ONE collective
    print(f"shard_acc done: {args.n} async chained executions + 1 reduce "
          f"in {time.time() - start:.1f}s (sum[0]={float(out[0]):.0f})",
          flush=True)


if __name__ == "__main__":
    main()
