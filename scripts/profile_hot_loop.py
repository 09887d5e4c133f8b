"""Attribute the fixed-effect hot loop's time on real hardware.

Round-2 bench measured 1.35% of HBM peak on the winning (scatter) path with
no explanation. This script times each constituent op of one L-BFGS
iteration at the bench shape (n=2^21, k=39, d=2^18) so the gap can be
attributed, and times candidate replacements for the gradient-side
transpose (hoisted CSC cumsum, segment-sum, one-shot scatter) measured in
isolation rather than buried inside a whole fit.

Writes a plain-text table to stdout; run it on the TPU.
Shapes shrink automatically on CPU so the script doubles as a smoke test.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def bench(fn, warmup=2, reps=5):
    """Median wall-clock of fn(salt).

    ``fn`` must build a call whose inputs DEPEND on the float ``salt`` (e.g.
    perturb a float operand by it), so that no timed call is bit-identical
    to its warm-up.  Sync is a scalar device->host fetch of the result,
    which cannot complete before the computation has actually run.
    """
    def once(salt):
        t0 = time.perf_counter()
        out = fn(jnp.float32(salt))
        leaf = jax.tree_util.tree_leaves(out)[0]
        float(jnp.sum(leaf))
        return time.perf_counter() - t0

    for i in range(warmup):
        once(1e-8 * (i + 1))
    ts = [once(1e-8 * (i + 17)) for i in range(reps)]
    return float(np.median(ts))


def main():
    platform = jax.devices()[0].platform
    if platform == "cpu":
        n, d, k = 1 << 15, 1 << 14, 39
    else:
        n, d, k = 1 << 21, 1 << 18, 39
    nnz = n * k
    print(f"platform={platform} n={n} d={d} k={k} nnz={nnz/1e6:.1f}M",
          flush=True)

    key = jax.random.key(0)

    @jax.jit
    def make(key):
        k_idx, k_w, k_d = jax.random.split(key, 3)
        indices = jax.random.randint(k_idx, (n, k), 0, d, jnp.int32)
        values = jnp.ones((n, k), jnp.float32)
        w = jax.random.normal(k_w, (d,), jnp.float32)
        dvec = jax.random.normal(k_d, (n,), jnp.float32)
        labels = (dvec > 0).astype(jnp.float32)
        return indices, values, w, dvec, labels

    indices, values, w, dvec, labels = jax.block_until_ready(make(key))

    results = {}
    bw_peak = 8.19e11

    def record(name, fn, traffic_bytes=None, **kw):
        """Bench fn(salt), store + print the line IMMEDIATELY (a later
        wedge must not lose earlier measurements)."""
        t = bench(fn, **kw)
        results[name] = t
        line = f"{name:32s} {t*1e3:10.2f} ms"
        if traffic_bytes:
            bw = traffic_bytes / t
            line += f"   ~{bw/1e9:7.1f} GB/s ({bw/bw_peak:.1%} of peak)"
        print(line, flush=True)
        return t

    tb = 16.0 * nnz  # 2x(idx+val) int32/f32 traffic model

    # ---- forward: margin gather --------------------------------------------
    @jax.jit
    def margin(w, indices, values):
        return jnp.sum(values * w[indices], axis=1)

    record("margin gather  (fwd pass)",
           lambda s: margin(w + s, indices, values), tb)

    # ---- pointwise loss on margins (line-search trial cost in margin space)
    @jax.jit
    def pointwise(m, labels):
        return jnp.sum(jax.nn.softplus(jnp.where(labels > 0, -m, m)))

    m0 = margin(w, indices, values)
    record("pointwise loss (O(n) only)",
           lambda s: pointwise(m0 + s, labels))

    # ---- backward: scatter-add transpose -----------------------------------
    @jax.jit
    def scatter_t(indices, values, dvec):
        contrib = values * dvec[:, None]
        return jnp.zeros((d,), jnp.float32).at[indices.reshape(-1)].add(
            contrib.reshape(-1))

    record("scatter X^T d  (bwd pass)",
           lambda s: scatter_t(indices, values, dvec + s), tb)

    # ---- full value_and_grad (what one line-search eval costs today) -------
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.types import LabeledBatch, SparseFeatures

    obj = make_objective("logistic")
    batch = LabeledBatch(
        SparseFeatures(indices, values, dim=d), labels,
        jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32))
    # pass the batch as an ARGUMENT: a closure would embed the 82M-element
    # arrays as HLO constants of the executable
    fg = jax.jit(lambda w, b: obj.value_and_grad(w, b, 1.0))
    record("value_and_grad (one fg eval)", lambda s: fg(w + s, batch))

    # ---- CSC build (the cost round 2 paid inside every fit) ----------------
    @jax.jit
    def csc_build(indices, values):
        flat = indices.reshape(-1)
        order = jnp.argsort(flat)
        return (values.reshape(-1)[order], (order // k).astype(jnp.int32),
                jnp.searchsorted(flat[order],
                                 jnp.arange(d + 1, dtype=jnp.int32)))

    @jax.jit
    def csc_build_s(idx, v, s):
        # salt one output inside the jit (an eager 82M `v + s` add would
        # inflate the timed traffic); all three outputs stay live
        sv, rows, cs = csc_build(idx, v)
        return sv + s, rows, cs

    record("csc build (argsort 82M)",
           lambda s: csc_build_s(indices, values, s))
    s_vals, s_rows, col_starts = jax.block_until_ready(csc_build(indices, values))

    # ---- hoisted CSC apply: gather + cumsum + boundary diff ----------------
    @jax.jit
    def csc_apply(s_vals, s_rows, col_starts, dvec):
        contrib = s_vals * dvec[s_rows]
        prefix = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                                  jnp.cumsum(contrib)])
        return prefix[col_starts[1:]] - prefix[col_starts[:-1]]

    record("csc apply (cumsum, hoisted)",
           lambda s: csc_apply(s_vals, s_rows, col_starts, dvec + s), tb)

    # ---- segment-sum variant on the sorted view ----------------------------
    sorted_ids = jax.block_until_ready(
        jax.jit(lambda idx: jnp.sort(idx.reshape(-1)))(indices))

    @jax.jit
    def seg_apply(s_vals, s_rows, sorted_ids, dvec):
        contrib = s_vals * dvec[s_rows]
        return jax.ops.segment_sum(contrib, sorted_ids, num_segments=d,
                                   indices_are_sorted=True)

    record("segment_sum (sorted ids)",
           lambda s: seg_apply(s_vals, s_rows, sorted_ids, dvec + s), tb)

    # ---- implicit-ones variants (bench layout: no values array) ------------
    @jax.jit
    def margin_binary(w, indices):
        return jnp.sum(w[indices], axis=1)

    record("margin gather (implicit 1s)",
           lambda s: margin_binary(w + s, indices), tb / 2)

    @jax.jit
    def scatter_binary(indices, dvec):
        contrib = jnp.broadcast_to(dvec[:, None], indices.shape)
        return jnp.zeros((d,), jnp.float32).at[indices.reshape(-1)].add(
            contrib.reshape(-1))

    record("scatter X^T d (implicit 1s)",
           lambda s: scatter_binary(indices, dvec + s), tb / 2)

    @jax.jit
    def seg_binary(s_rows, sorted_ids, dvec):
        return jax.ops.segment_sum(dvec[s_rows], sorted_ids, num_segments=d,
                                   indices_are_sorted=True)

    record("segment_sum (implicit 1s)",
           lambda s: seg_binary(s_rows, sorted_ids, dvec + s), tb / 2)

    # ---- fused Pallas apply (compiled Mosaic on TPU; interpret on CPU) -----
    from photon_ml_tpu.ops.pallas_kernels import csc_transpose_apply_pallas
    from photon_ml_tpu.types import CSCTranspose

    csc_view = CSCTranspose(values=s_vals, rows=s_rows,
                            col_starts=col_starts)
    pallas_j = jax.jit(lambda c, dv: csc_transpose_apply_pallas(c, dv))
    try:
        record("pallas fused apply" + (" [interp]" if platform == "cpu"
                                       else ""),
               lambda s: pallas_j(csc_view, dvec + s), tb)
    except Exception as e:  # a Mosaic compile failure must not kill the run
        print(f"pallas apply failed: {e}", flush=True)

    # ---- cumsum alone (is XLA's cumsum multi-pass?) ------------------------
    flat_contrib = jax.block_until_ready(
        jax.jit(lambda v, r, dv: v * dv[r])(s_vals, s_rows, dvec))
    # salt the OUTPUT inside the jitted kernel: an eager `big + s` add
    # would double the timed region's memory traffic
    cumsum_j = jax.jit(lambda x, s: jnp.cumsum(x) + s)
    record("cumsum 82M alone", lambda s: cumsum_j(flat_contrib, s))
    gather_j = jax.jit(lambda dv, r: dv[r])
    record("gather d[rows] alone", lambda s: gather_j(dvec + s, s_rows))

    # ---- the full bench fit, for eval accounting ---------------------------
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.parallel.data_parallel import fit_distributed
    from photon_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    w0 = jnp.zeros((d,), jnp.float32)
    iters = 10

    # the fit mirrors bench.py: implicit-ones layout + margin line search
    bin_batch = LabeledBatch(
        SparseFeatures(indices, None, dim=d), labels,
        jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32))

    def fit(salt):
        res = fit_distributed(
            obj, bin_batch, mesh, w0 + salt, l2=1.0, optimizer="lbfgs",
            config=OptimizerConfig(max_iters=iters, tolerance=0.0),
            sparse_grad="scatter")
        return res

    res = fit(jnp.float32(0.0))  # compile
    n_done = int(res.iterations)
    t_fit = record(f"full lbfgs fit ({n_done} iters)", fit,
                   warmup=1, reps=3)

    # ------------------------------------------------------------------------
    t_fg = results["value_and_grad (one fg eval)"]
    n_it = n_done
    print(f"\nfit/iter = {t_fit/max(n_it,1)*1e3:.2f} ms; fg eval = "
          f"{t_fg*1e3:.2f} ms -> fg-equivalents/iter = "
          f"{t_fit/max(n_it,1)/t_fg:.2f} (margin line search: ~1 gather + "
          "1 scatter per iteration expected)")


if __name__ == "__main__":
    main()
