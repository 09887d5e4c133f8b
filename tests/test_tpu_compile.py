"""Real TPU compiles of the main path's programs, without a chip.

``tests/test_tpu_lowering.py`` stops at ``jax.export`` lowering. These go
the rest of the way: the installed TPU compiler compiles each program for
a *described* v5e 2x2 (``jax.experimental.topologies``), at the shapes
``chip_smoke.py`` runs, so a kernel the chip's compiler refuses (tiling,
VMEM, a program that does not fit HBM) fails here at no chip time.
Nothing executes: these say nothing about results or times.

The topology is described inside a module-scoped fixture — never at
import — and every compile runs in the test's own process: only one
process may hold libtpu, and under xdist every worker imports this file.
Code that asks ``jax.default_backend()`` sees the CPU here, so the
chip's choices (``csc_pallas``, the vector gather, ``newton``) are passed
explicitly.
"""

import contextlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import LabeledBatch, SparseFeatures

# chip_smoke.py's glm phase: Criteo-shaped hashed features, explicit values
# (what the glm driver builds from a LIBSVM file)
ROWS, DIM, K = 1 << 17, 1 << 18, 39
f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / already held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def chip_settings():
    """What the program sees on the chip: f32 (conftest turns x64 on for
    the parity tests; Mosaic has no i64) and the gather mode that "auto"
    resolves to there."""
    from photon_ml_tpu import types as T

    prev = T.gather_mode()
    T.set_gather_mode("vector")
    try:
        with jax.enable_x64(False):
            yield
    finally:
        T.set_gather_mode(prev)


@pytest.fixture(autouse=True)
def _chip_settings():
    with chip_settings():
        yield


def _compile_fit(mesh):
    """Compile one whole csc_pallas L-BFGS fit for ``mesh`` from shapes."""
    from photon_ml_tpu.parallel.data_parallel import fit_distributed

    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=20, tolerance=1e-7)

    def fit(w0, indices, values, labels, offsets, weights):
        batch = LabeledBatch(SparseFeatures(indices, values, dim=DIM),
                             labels, offsets, weights)
        r = fit_distributed(obj, batch, mesh, w0, l2=1.0, config=cfg,
                            optimizer="lbfgs", sparse_grad="csc_pallas")
        return r.w, r.value

    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    s = jax.ShapeDtypeStruct
    row = s((ROWS,), f32, sharding=rows)
    return jax.jit(fit).lower(
        s((DIM,), f32, sharding=rep), s((ROWS, K), i32, sharding=rows),
        s((ROWS, K), f32, sharding=rows), row, row, row).compile()


@pytest.mark.parametrize("rows", [1 << 17, 1 << 21])
def test_multiply_prefix_sum_compiles(one_chip, rows):
    from photon_ml_tpu.ops.pallas_kernels import multiply_prefix_sum

    v = jax.ShapeDtypeStruct((rows * K,), f32, sharding=one_chip)
    compiled = multiply_prefix_sum.lower(v, v).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def one_chip_fit(topo):
    with chip_settings():  # module scope: set up before the autouse one
        return _compile_fit(make_mesh({"data": 1}, devices=topo.devices[:1]))


def test_glm_fit_compiles_on_one_chip(one_chip_fit):
    compiled = one_chip_fit
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_glm_fit_compiles_on_four_chips(topo, one_chip_fit):
    one = one_chip_fit
    four = _compile_fit(make_mesh({"data": 4}, devices=topo.devices))
    text = four.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    # rows are laid out over the four chips: only w0 is replicated
    ratio = (four.memory_analysis().argument_size_in_bytes
             / one.memory_analysis().argument_size_in_bytes)
    assert 0.24 < ratio < 0.30, ratio


def test_newton_re_solver_compiles_at_one_block(topo):
    """One real block of the batched dense-Newton solver: the widest
    program the GAME phase can hand the compiler, inside the bytes
    ``entity_bytes`` budgets for it."""
    from photon_ml_tpu.game.random_effect import (
        _jitted_sharded_solver,
        block_entities,
        entity_bytes,
    )

    D_loc, rows = 32, 64
    # as many entities as the block's byte budget holds at this shape
    E = block_entities(1 << 20, entity_bytes(rows, D_loc, D_loc, 4, "newton"))
    mesh = make_mesh({"entity": 1}, devices=topo.devices[:1])
    run = _jitted_sharded_solver(
        D_loc, "logistic", "newton",
        OptimizerConfig(max_iters=30, tolerance=1e-6),
        False, mesh, "entity", 0)
    ent = NamedSharding(mesh, P("entity"))
    rep = NamedSharding(mesh, P())

    def s(shape, dtype=f32, sharding=ent):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    compiled = run.lower(
        s((E, rows, D_loc), i32), s((E, rows, D_loc)),
        s((E, rows)), s((E, rows)), s((E, rows)), s((E, D_loc)),
        s((E, 1)), s((E, 1)), s((), sharding=rep),
        s((), sharding=rep)).compile()
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("variance", [False, "full"])
@pytest.mark.parametrize("E,D_loc,rows,k", [(8858, 21, 128, 11),
                                            (445, 36, 1024, 5)])
def test_newton_re_solver_holds_no_linalg_custom_call(one_chip, E, D_loc,
                                                      rows, k, variance):
    """The Newton step's solve (and the inverse ``compute_variance="full"``
    reads) is plain element-wise XLA at the GLMix cell's bucket shapes: a
    batched ``jnp.linalg.solve`` compiles to ``LuDecompositionBlock`` and
    ``InvertDiagBlocks*Triangular`` custom calls, one small matrix at a
    time."""
    from photon_ml_tpu.game.random_effect import _newton_dense_solver

    solver = _newton_dense_solver(
        D_loc, "logistic", OptimizerConfig(max_iters=4, tolerance=0.0),
        variance)

    def s(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(solver).lower(
        s((E, rows, k), i32), s((E, rows, k)), s((E, rows)), s((E, rows)),
        s((E, rows)), s((E, D_loc)), s((E, 1)), s((E, 1)), s(()),
        s(())).compile().as_text()
    targets = set(re.findall(r'custom_call_target="([^"]+)"', text))
    assert not [t for t in targets
                if re.search("Lu|Triangular|Cholesky|Qr|Eigh", t)], targets
    assert "photon.re/newton/solve" in text


def test_serving_fused_score_compiles(one_chip):
    """Serving's one-call score at the serving driver's defaults
    (--re-pages 4 --re-page-rows 256 --pad-nnz 64, row bucket 256): a
    fixed-effect margin plus one paged random-effect gather."""
    from photon_ml_tpu.ops.pallas_kernels import paged_gather_score
    from photon_ml_tpu.types import margins

    B, k, slots, d_fixed, d_re = 256, 64, 4 * 256, DIM, 4096

    def score(offsets, idx_f, val_f, w, idx_r, val_r, table, slot):
        m = margins(SparseFeatures(idx_f, val_f, dim=d_fixed), w)
        return offsets + m + paged_gather_score(table, slot, idx_r, val_r)

    def s(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(score).lower(
        s((B,)), s((B, k), i32), s((B, k)), s((d_fixed,)),
        s((B, k), i32), s((B, k)), s((slots, d_re)),
        s((B,), i32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


# X^T d at the sizes of the benchmark's cells: (rows, columns, features a
# row) -> row gathers in the program (d[rows], then `lp` run by run)
XTD_CELLS = {
    "criteo-lr": ((1 << 19, 1 << 24, 39), 2),  # 81.8 MB of prefixes: whole
    "criteo-lr-tron": ((1 << 20, 1 << 24, 39), 3),  # 163.6 MB: in two runs
    "glmix-ml20m": ((1 << 22, 1 << 20, 12), 4),  # 201.3 MB: in three runs
}


@pytest.mark.parametrize("cell", list(XTD_CELLS))
def test_row_gathers_read_tables_in_fast_memory(one_chip, cell):
    """A row gather costs 1.8 ns a row out of a table the TPU compiler
    keeps in its fast memory space (``S(1)`` in a compiled layout) and
    9-15 ns out of one it leaves in HBM (PERF.md section 7.7, measured):
    ``table_gather`` reads a table too large for that space in runs that
    fit. Held here on the compiled ``X^T d`` of every cell's size: each
    row gather's table carries ``S(1)``."""
    from photon_ml_tpu.ops.pallas_kernels import csc_transpose_apply_pallas
    from photon_ml_tpu.types import CSCTranspose

    (rows, dim, k), gathers = XTD_CELLS[cell]

    def xtd(csc_rows, col_starts, d):
        return csc_transpose_apply_pallas(
            CSCTranspose(values=None, rows=csc_rows, col_starts=col_starts),
            d)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(xtd).lower(s((rows * k,), i32), s((dim + 1,), i32),
                              s((rows,), f32)).compile().as_text()
    layouts = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ", text,
                              re.M))
    tables = [layouts[table] for table in re.findall(
        r"= f32\[\d+,128\]\S* fusion\((%[\w.\-]+), [^\n]*kind=kCustom"
        r"[^\n]*/rows/jit\(_take\)/gather", text)]
    assert len(tables) == gathers, tables
    for layout in tables:
        assert re.fullmatch(r"f32\[\d+,128\]\{1,0:T\(8,128\)S\(1\)\}",
                            layout), tables


# the Criteo cells' width, history and passes (benchmark/configs/
# criteo-lr.json, criteo-enet.json). Rows are cut from 2^19: the history
# holds m slots of d whatever the rows are, and the compile's time is
# linear in them
HIST_DIM, HIST_M, HIST_ROWS = 1 << 24, 10, 1 << 12
HISTORY_FITS = {
    "lbfgs_margin": dict(optimizer="lbfgs", line_search="margin"),
    "owlqn": dict(optimizer="owlqn", l1=1.0, line_search="full"),
}


@pytest.mark.parametrize("fit", list(HISTORY_FITS))
def test_history_slot_is_contiguous_in_hbm(topo, fit):
    """The TPU tiles an array's two minor dimensions (8, 128). With the
    (s, y) history as ``[m, d]`` the slot index is the sublane axis: a slot
    is one row in eight of every tile, ``two_loop_direction`` copies each
    slot it reads out as ``f32[1, d]{T(1,128)}`` at eight times its bytes
    (0.85 ms a slot at d = 2^24 on the v5e; PERF.md section 6, PR 35), and
    the pair's store is a read-modify-write of eight rows. Held here on the
    compiled L-BFGS-margin and OWL-QN fits at the cells' width: nothing
    under the two-loop or the store produces a ``[1, d]`` slice, and no
    loop carries a history whose slot index is a tiled dimension."""
    from photon_ml_tpu.parallel.data_parallel import fit_distributed

    mesh = make_mesh({"data": 1}, devices=topo.devices[:1])
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=10, tolerance=0.0, history=HIST_M)

    def run(w0, indices, labels, offsets, weights):
        batch = LabeledBatch(SparseFeatures(indices, None, dim=HIST_DIM),
                             labels, offsets, weights)
        r = fit_distributed(obj, batch, mesh, w0, l2=1.0, config=cfg,
                            sparse_grad="csc_pallas", **HISTORY_FITS[fit])
        return r.w, r.value

    rows = NamedSharding(mesh, P("data"))
    s = jax.ShapeDtypeStruct
    row = s((HIST_ROWS,), f32, sharding=rows)
    text = jax.jit(run).lower(
        s((HIST_DIM,), f32, sharding=NamedSharding(mesh, P())),
        s((HIST_ROWS, K), i32, sharding=rows), row, row, row,
    ).compile().as_text()

    scopes = ("photon.lbfgs/two_loop", "photon.lbfgs/update",
              "photon.owlqn/update")
    scoped = [line for line in text.splitlines()
              if re.search(r'op_name="[^"]*(?:%s)' % "|".join(scopes), line)]
    assert any("photon.lbfgs/two_loop" in line for line in scoped)
    slices = [m.group(1, 2) for m in (
        re.match(r"\s*(?:ROOT )?(%%[\w.\-]+) = (f32\[1,%d\]\S*) " % HIST_DIM,
                 line) for line in scoped) if m]
    assert not slices, slices

    # every array a while loop carries that is large enough to be a history
    carried = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\(.*\)) while\(", line)
        if m:
            carried.update(re.findall(r"f32\[([\d,]+)\]\{([\d,]+):", m[1]))
    histories = []
    for dims, minor_to_major in carried:
        dims = [int(x) for x in dims.split(",")]
        if math.prod(dims) >= HIST_M * HIST_DIM:
            tiled = [dims[int(i)] for i in minor_to_major.split(",")[:2]]
            histories.append((dims, tiled))
    assert histories, "no loop carries a history"
    on_tile = [h for h in histories if len(h[0]) > 1 and HIST_M in h[1]]
    assert not on_tile, on_tile


def test_poisson_tron_fit_compiles_at_the_cells_shapes(topo):
    """``criteo-poisson-tron.fit``'s program (ISSUE 36) at the cell's own
    shapes: 2^20 rows of 39 implicit ones over 2^24 columns, float32, six
    outer iterations under ``tolerance=0``, ``csc_pallas``; the column sort
    is compiled into the same program here (the cell hands the fit a
    precomputed view). About 40 s: with implicit ones the flatten that makes
    the L-BFGS compile above slow has no values to move."""
    from photon_ml_tpu.parallel.data_parallel import fit_distributed

    rows, dim = 1 << 20, 1 << 24
    mesh = make_mesh({"data": 1}, devices=topo.devices[:1])
    obj = make_objective("poisson")
    cfg = OptimizerConfig(max_iters=6, tolerance=0.0)

    def fit(w0, indices, counts, log_exposures, weights):
        batch = LabeledBatch(SparseFeatures(indices, None, dim=dim),
                             counts, log_exposures, weights)
        r = fit_distributed(obj, batch, mesh, w0, l2=1.0, config=cfg,
                            optimizer="tron", sparse_grad="csc_pallas")
        return r.w, r.value, r.cg_steps, r.rejected_steps, r.precond_passes

    on_rows = NamedSharding(mesh, P("data"))
    s = jax.ShapeDtypeStruct
    row = s((rows,), f32, sharding=on_rows)
    compiled = jax.jit(fit).lower(
        s((dim,), f32, sharding=NamedSharding(mesh, P())),
        s((rows, K), i32, sharding=on_rows), row, row, row).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the Pallas prefix sum
    assert "exponential" in text  # the Poisson loss and its d2, unclamped
    for scope in ("photon.tron/trial", "photon.tron/hvp",
                  "photon.tron/precond", "photon.tron/cg"):
        assert scope in text, scope
    mem = compiled.memory_analysis()
    print(mem)
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    assert 0.125 * 16 * 2 ** 30 < peak < 16e9  # over the cell's floor


# (loss, outer iterations) of the two TRON cells (benchmark/configs/
# criteo-lr-tron.json, criteo-poisson-tron.json): 2^20 rows x 39 over 2^24
TRON_CELLS = {"criteo-lr-tron": ("logistic", 1),
              "criteo-poisson-tron": ("poisson", 6)}


@pytest.mark.parametrize("cell", list(TRON_CELLS))
def test_tron_evaluates_its_second_order_oracle_once_an_iterate(topo, cell):
    """``photon_fit_tron`` over a precomputed view, as the cells run it
    (ISSUE 37), read off the compiled text. The curvature ``d2(w)`` is
    carried, so an HVP holds two row gathers (``X v`` and ``dv[rows]``: the
    parent's third was ``X w`` again) beside its combine's ``lp``; the
    Jacobi diagonal is a transpose of ``d2`` through the view, so nothing
    sorts (XLA's scatter-add of 40.9M updates did, 467 ms a diagonal on
    the chip) and the only scatters left write a combine's <= B spanning
    columns; every gathered table has the fast memory space, the 4 MB
    ``d2`` among them; and no ``[rows]`` vector is copied in the program:
    the CG loop reads ``d2`` out of the outer loop's state in place. About
    10 s a cell: no column sort to compile."""
    from photon_ml_tpu.parallel.data_parallel import fit_distributed
    from photon_ml_tpu.types import CSCTranspose

    task, iters = TRON_CELLS[cell]
    rows, dim = 1 << 20, 1 << 24
    mesh = make_mesh({"data": 1}, devices=topo.devices[:1])
    obj = make_objective(task)
    cfg = OptimizerConfig(max_iters=iters, tolerance=0.0)

    def fit(w0, indices, labels, offsets, weights, csc_rows, col_starts):
        batch = LabeledBatch(SparseFeatures(indices, None, dim=dim),
                             labels, offsets, weights)
        r = fit_distributed(
            obj, batch, mesh, w0, l2=1.0, config=cfg, optimizer="tron",
            sparse_grad="csc_pallas", precomputed_csc=CSCTranspose(
                values=None, rows=csc_rows, col_starts=col_starts))
        return r.w, r.value, r.cg_steps, r.precond_passes, r.curvature_passes

    on_rows = NamedSharding(mesh, P("data"))
    s = jax.ShapeDtypeStruct
    row = s((rows,), f32, sharding=on_rows)
    text = jax.jit(fit).lower(
        s((dim,), f32, sharding=NamedSharding(mesh, P())),
        s((rows, K), i32, sharding=on_rows), row, row, row,
        s((1, rows * K), i32, sharding=on_rows),
        s((1, dim + 1), i32, sharding=on_rows)).compile().as_text()

    assert "photon.tron/curvature" in text and "tpu_custom_call" in text
    assert " sort(" not in text and "scatter-add" not in text
    scatters = re.findall(r'scatter\([^\n]*op_name="([^"]*)"', text)
    assert scatters and all(
        name.endswith("photon.csc/boundary_combine/span/scatter")
        for name in scatters), scatters
    assert not re.search(r"= f32\[%d\]\S* copy\(" % rows, text)

    layouts = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ", text,
                              re.M))
    # (table, name stack above the gather's own scopes) of each row gather
    gathers = [(layouts[table], site) for table, site in re.findall(
        r"= f32\[\d+,128\]\S* fusion\((%[\w.\-]+), [^\n]*kind=kCustom"
        r'[^\n]*op_name="([^"]*)/rows/jit\(_take\)/gather', text)]
    for layout, site in gathers:
        assert re.fullmatch(r"f32\[\d+,128\]\{1,0:T\(8,128\)S\(1\)\}",
                            layout), (layout, site)

    def tables(scope, kernel):
        return sorted(int(re.match(r"f32\[(\d+),", layout)[1]) * 128
                      for layout, site in gathers
                      if f"{scope}/{kernel}" in site)

    halves = [-(-(rows * K) // (2 * 128)) * 128] * 2  # `lp`'s two runs
    assert tables("photon.tron/hvp", "photon.table_gather") == [rows, dim]
    assert tables("photon.tron/hvp", "photon.csc") == halves
    assert tables("photon.tron/trial", "photon.table_gather") == [rows, dim]
    # the diagonal gathers d2[rows] and nothing of w's length: at w0, and
    # in the branch of the loop's ``cond`` that runs where a step is
    # accepted and another iteration follows (never with one iteration:
    # ``curvature_passes``, held on the CPU, says how often)
    assert tables("photon.tron/precond", "photon.table_gather") == [rows] * 2
    # the curvature gathers nothing: w0's reads the margins of (f0, g0),
    # the loop's those of the accepted trial point (``margins_reused``)
    assert tables("photon.tron/curvature", "photon.table_gather") == []
    # (f0, g0), an HVP and the trial point: a product gather, d[rows] and
    # `lp`'s two runs each; a diagonal: d2[rows] and `lp`'s two
    assert len(gathers) == 3 * 4 + 2 * 3


def test_owlqn_gathers_an_accepted_point_once(topo):
    """``photon_fit_owlqn`` over a precomputed view at ``criteo-enet.fit``'s
    shapes (2^19 rows of 39 implicit ones over 2^24 columns, float32, ten
    iterations under ``tolerance=0``), compiled as ``fit_distributed``
    calls it (its own jit, no caller's around it) and read off the text. A
    trial's value gathers its margins ``X w`` under
    ``photon.owlqn/line_search`` and the gradient at the accepted point
    reads them: the loop holds one gather of the 2^24 table, ``(f0, g0)``
    the other, and each gradient (``g0``'s, the accepted point's) one
    ``d[rows]``. Every gathered table has the fast memory space, ``w0``'s
    among them (left in HBM while the accepted point was gathered again:
    202.5 ms where the loop's take 36.8 on the v5e). The margins ride in
    the loops' state: no ``[rows]`` vector is copied. About 15 s."""
    from photon_ml_tpu.parallel import data_parallel as dp
    from photon_ml_tpu.types import CSCTranspose

    rows, dim = 1 << 19, 1 << 24
    mesh = make_mesh({"data": 1}, devices=topo.devices[:1])
    cfg = OptimizerConfig(max_iters=10, tolerance=0.0)
    _, make = dp._black_box_fit(make_objective("logistic"), mesh, "data",
                                "owlqn", cfg, "csc_pallas", True)
    on_rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    s = jax.ShapeDtypeStruct
    row = s((rows,), f32, sharding=on_rows)
    batch = LabeledBatch(SparseFeatures(s((rows, K), i32, sharding=on_rows),
                                        None, dim=dim), row, row, row)
    view = CSCTranspose(values=None,
                        rows=s((1, rows * K), i32, sharding=on_rows),
                        col_starts=s((1, dim + 1), i32, sharding=on_rows))
    text = jax.jit(make()).lower(
        s((dim,), f32, sharding=rep), batch, s((), f32, sharding=rep),
        s((), f32, sharding=rep), view).compile().as_text()

    assert not re.search(r"= f32\[%d\]\S* copy\(" % rows, text)
    layouts = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ", text,
                              re.M))
    gathers = [(layouts[table], site) for table, site in re.findall(
        r"= f32\[\d+,128\]\S* fusion\((%[\w.\-]+), [^\n]*kind=kCustom"
        r'[^\n]*op_name="([^"]*)/rows/jit\(_take\)/gather', text)]
    for layout, site in gathers:
        assert re.fullmatch(r"f32\[\d+,128\]\{1,0:T\(8,128\)S\(1\)\}",
                            layout), (layout, site)
    sizes = [(int(re.match(r"f32\[(\d+),", layout)[1]) * 128, site)
             for layout, site in gathers]
    of_w = [site for size, site in sizes if size == dim]
    assert len(of_w) == 2, sizes
    assert sum("photon.owlqn/line_search/" in site for site in of_w) == 1
    # each gradient: d[rows], and `lp` over the 81.8 MB of prefixes whole
    assert sorted(size for size, _ in sizes if size != dim) == (
        [rows] * 2 + [rows * K] * 2), sizes
