"""TPU-target lowering certification (chip readiness without a chip).

``jax.export(..., platforms=["tpu"])`` runs the full StableHLO (and
Pallas->Mosaic) lowering for the TPU target from a CPU host — the layer
interpret-mode execution parity can never exercise. Round 4 this caught
two chip-blocking kernel bugs (docs/PERF.md "Round-4 Mosaic lowering"),
so every distributed hot-path program is pinned here: a chip run must
start at "compile", not "debug the lowering" (VERDICT r3 #4).

These certify LOWERING only; the TPU compiler's own pass over the main
path's programs is tests/test_tpu_compile.py, and the numerics need the
chip (chip_smoke.py).
"""

import jax
import jax.numpy as jnp
import pytest
from jax import export

from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.parallel.data_parallel import fit_distributed
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import LabeledBatch, SparseFeatures

N, D, K = 2048, 512, 8


def _fit_exporter(mesh_axes={"data": 8}, **kw):
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=4, tolerance=0.0)
    mesh = make_mesh(dict(mesh_axes))

    def f(w0, indices, labels):
        batch = LabeledBatch(
            SparseFeatures(indices, None, dim=D), labels,
            jnp.zeros((N,), jnp.float32), jnp.ones((N,), jnp.float32))
        r = fit_distributed(obj, batch, mesh, w0, l2=0.5, config=cfg, **kw)
        return r.w, r.value

    return export.export(jax.jit(f), platforms=["tpu"])(
        jax.ShapeDtypeStruct((D,), jnp.float32),
        jax.ShapeDtypeStruct((N, K), jnp.int32),
        jax.ShapeDtypeStruct((N,), jnp.float32))


@pytest.mark.parametrize("kw", [
    dict(optimizer="lbfgs"),                             # margin + scatter
    dict(optimizer="lbfgs", sparse_grad="csc"),
    dict(optimizer="tron", line_search="full"),
    dict(optimizer="owlqn", line_search="full"),
], ids=lambda kw: "-".join(str(v) for v in kw.values()))
def test_distributed_fit_lowers_for_tpu(kw):
    exp = _fit_exporter(**kw)
    assert exp.nr_devices == 8


def test_sharded_csc_pallas_lowers_with_mosaic_kernel():
    """Under shard_map, lax.platform_dependent must still pick the REAL
    Mosaic kernel for the TPU target (not the interpret branch)."""
    exp = _fit_exporter(optimizer="lbfgs", sparse_grad="csc_pallas")
    assert exp.nr_devices == 8
    assert "tpu_custom_call" in exp.mlir_module()


def test_newton_re_solver_lowers_for_tpu():
    """The batched dense-Newton RE solver (einsum Hessians + batched SPD
    solve) under an entity-axis shard_map lowers for TPU."""
    from photon_ml_tpu.game.random_effect import _jitted_sharded_solver

    E, D_loc, rows = 16, 6, 32
    run = _jitted_sharded_solver(
        D_loc, "logistic", "newton",
        OptimizerConfig(max_iters=5, tolerance=1e-6),
        False, make_mesh({"entity": 8}), "entity", 0)
    s = jax.ShapeDtypeStruct
    exp = export.export(run, platforms=["tpu"])(
        s((E, rows, D_loc), jnp.int32), s((E, rows, D_loc), jnp.float32),
        s((E, rows), jnp.float32), s((E, rows), jnp.float32),
        s((E, rows), jnp.float32), s((E, D_loc), jnp.float32),
        s((E, 1), jnp.float32), s((E, 1), jnp.float32),
        s((), jnp.float32), s((), jnp.float32))
    assert exp.nr_devices == 8


def test_fixed_fit_lowers_on_two_axis_game_mesh():
    """The GAME CD loop runs the fixed-effect fit on the 'data' axis of a
    2-axis (data x entity) mesh — axis-name handling must lower for TPU
    with the extra axis present."""
    exp = _fit_exporter(mesh_axes={"data": 2, "entity": 4},
                        sparse_grad="csc")
    assert exp.nr_devices == 8


def test_streamed_chunk_kernels_lower_for_tpu_collective_free():
    """The streamed per-chunk kernels (fg / hvp / diag / ladder trial)
    must lower for TPU with ZERO collectives in the chunk program — the
    per-device-partials design (streaming._shard_map_chunk) that fixed
    the XLA:CPU rendezvous deadlock is also the one-all-reduce-per-pass
    ICI cost model; a collective sneaking back in (e.g. check_vma
    auto-psum) would silently restore both problems."""
    from photon_ml_tpu.ops.losses import apply_weights, mask_margins  # noqa
    from photon_ml_tpu.optimize import OptimizerConfig as Cfg
    from photon_ml_tpu.parallel.data_parallel import cached_jit
    from photon_ml_tpu.parallel.streaming import (
        fit_streaming,
        streaming_hessian_diagonal,
        streaming_hvp,
        streaming_value_and_grad,
    )

    obj = make_objective("logistic")
    mesh = make_mesh({"data": 8})
    rows = 256
    # instantiate every cached kernel (empty chunk lists: the kernels are
    # built before iteration, and lowering needs only their closures)
    streaming_value_and_grad(obj, [], D, mesh=mesh)
    streaming_hvp(obj, [], D, mesh=mesh)
    streaming_hessian_diagonal(obj, [], D, jnp.zeros((D,)), mesh=mesh)
    fit_streaming(obj, [], D, config=Cfg(max_iters=1, tolerance=0.0),
                  mesh=mesh)  # builds the margin trial ladder kernel
    s = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32

    def assert_no_collective(exp, name):
        mlir = exp.mlir_module()
        for spelling in ("all_reduce", "all-reduce", "psum"):
            assert spelling not in mlir, f"{name}: {spelling} found"

    batch_args = (s((rows, K), i32), (), s((rows,), f32),
                  s((rows,), f32), s((rows,), f32))
    fg_k = cached_jit(obj, ("stream_fg", mesh, "data", D), lambda: None)
    exp = export.export(fg_k, platforms=["tpu"])(
        s((D,), f32), *batch_args,
        s((8,), f32), s((8,), f32), s((8, D), f32), s((8, D), f32))
    assert exp.nr_devices == 8
    assert_no_collective(exp, "stream_fg")
    hvp_k = cached_jit(obj, ("stream_hvp", mesh, "data", D), lambda: None)
    assert_no_collective(export.export(hvp_k, platforms=["tpu"])(
        (s((D,), f32), s((D,), f32)), *batch_args,
        s((8, D), f32), s((8, D), f32)), "stream_hvp")
    diag_k = cached_jit(obj, ("stream_diag", mesh, "data", D), lambda: None)
    assert_no_collective(export.export(diag_k, platforms=["tpu"])(
        s((D,), f32), *batch_args,
        s((8, D), f32), s((8, D), f32)), "stream_diag")
    L = 8  # default ladder width (min(max_line_search_steps, 8))
    trial_k = cached_jit(obj, ("stream_trial_delta_ladder", mesh, "data", L),
                         lambda: None)
    assert_no_collective(export.export(trial_k, platforms=["tpu"])(
        s((L,), f32), s((rows,), f32), s((rows,), f32), s((rows,), f32),
        s((rows,), f32), s((8, L), f32), s((8, L), f32)), "stream_trial")


def test_device_auc_evaluator_lowers_for_tpu():
    """The per-iteration device AUC (histogram form on a mesh, exact sort
    single-device) used for CD validation lowers for TPU."""
    from photon_ml_tpu.evaluation.device import make_device_evaluator

    mesh = make_mesh({"data": 8})
    fn = make_device_evaluator("auc", mesh)
    s = jax.ShapeDtypeStruct
    exp = export.export(jax.jit(fn), platforms=["tpu"])(
        s((N,), jnp.float32), s((N,), jnp.float32), s((N,), jnp.float32))
    assert exp.nr_devices == 8


def test_grouped_device_evaluators_lower_for_tpu():
    """The per_group_* device evaluators (lexsort + segment ops over
    factorized group ids) used for CD per-iteration monitoring lower for
    the TPU target."""
    import numpy as np

    from photon_ml_tpu.evaluation.device import make_grouped_device_evaluator

    groups = np.arange(N) % 17
    s = jax.ShapeDtypeStruct
    for name in ("per_group_auc", "per_group_logistic_loss",
                 "per_group_precision_at_5"):
        fn = make_grouped_device_evaluator(name, groups)
        exp = export.export(jax.jit(fn), platforms=["tpu"])(
            s((N,), jnp.float32), s((N,), jnp.float32), s((N,), jnp.float32))
        assert "stablehlo" in exp.mlir_module(), name


def test_vector_gather_fit_lowers_for_tpu():
    """The r05 vectorized table gather ('auto' on hardware). jax.export
    runs from a CPU host, where 'auto' traces the SCALAR branch — so the
    chip's actual path must be pinned to 'vector' explicitly here or the
    certification would silently cover the wrong program."""
    from photon_ml_tpu import types as T

    prev = T.gather_mode()
    T.set_gather_mode("vector")
    try:
        for kw in (dict(optimizer="lbfgs"),
                   dict(optimizer="lbfgs", sparse_grad="csc"),
                   dict(optimizer="lbfgs", sparse_grad="csc_pallas")):
            exp = _fit_exporter(**kw)
            assert exp.nr_devices == 8
    finally:
        T.set_gather_mode(prev)


def test_vector_gather_chunked_lowers_for_tpu():
    """The lax.map-chunked large-nnz form (bench shape takes it)."""
    from photon_ml_tpu import types as T

    prev = T.gather_mode()
    T.set_gather_mode("vector")
    old = T._GATHER_CHUNK
    T._GATHER_CHUNK = 1 << 12  # force chunking at test size
    try:
        def f(w, idx):
            return T.table_gather(w, idx).sum()

        exp = export.export(jax.jit(f), platforms=["tpu"])(
            jax.ShapeDtypeStruct((1 << 14,), jnp.float32),
            jax.ShapeDtypeStruct((1 << 14, 8), jnp.int32))
        assert exp.platforms == ("tpu",)
    finally:
        T._GATHER_CHUNK = old
        T.set_gather_mode(prev)
