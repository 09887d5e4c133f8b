"""Smoke run of the main path on the attached TPU: does the system still start?

One process, no child that needs the chip. With no arguments (one chip):

  device  jax.devices() must be a TPU, else exit non-zero at once
  glm     Criteo-width hashed sparse logistic regression (dim 2^18, 39
          active features a row, f32, L-BFGS, L2) through
          ``photon_ml_tpu.cli.glm_driver.main`` on a LIBSVM file generated
          from --seed; rows are depth and are cut (2^17 + 2^14 held out;
          bench.py's shape has 2^21)
  game    ``game_training_driver.main`` on two-random-effect Avro data
          (fixed + per-user + per-item), then ``game_scoring_driver.main``
          on the held-out file
  serve   the saved model behind ``serving_driver.main`` (one replica, the
          asyncio front end), scored over HTTP and compared with the
          scoring driver's score for the same rows

``--chips 4`` runs only the data-parallel glm fit on a data=4 mesh and the
data=1 fit it is compared with. The seconds printed are a smoke's, not a
benchmark's. The last line of stdout is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit code is 0
only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

GLM_DIM = 1 << 18
GLM_K = 39
GLM_ROWS, GLM_HELDOUT = 1 << 17, 1 << 14  # depth, cut: bench.py has 2^21
GAME_ROWS, GAME_USERS = 50_000, 500
GLM_L2 = 1.0
GLM_MAX_ITERS = 200  # the CPU f32 fit of the same file converges in 95
GLM_AUC_MIN = 0.70
GAME_AUC_MIN = 0.75
SERVE_TOL = 1e-4


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- data, made from a seed ---------------------------------------------------

def glm_rows(rows: int, seed: int, dim: int = GLM_DIM, k: int = GLM_K):
    """Criteo-shaped rows: ``k`` categorical fields hashed into one ``dim``
    space, field values heavy-tailed (log-uniform rank), labels from a
    planted weight vector so the held-out AUC says something. The first
    row's first slot is pinned to the last column: the LIBSVM reader sizes
    the space by the largest id it sees.
    Returns (indices [rows, k] int32, labels [rows])."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=dim) * 0.5
    rank = np.exp(rng.random((rows, k)) * math.log(dim)).astype(np.int64)
    field = np.arange(k, dtype=np.int64)[None, :]
    indices = ((rank * 2654435761 + field * 40503) % dim).astype(np.int32)
    indices[0, 0] = dim - 1
    logits = w_true[indices].sum(axis=1)
    labels = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logits))).astype(int)
    return indices, labels


def write_libsvm(path: str, indices: np.ndarray, labels: np.ndarray) -> None:
    """One-hot rows as LIBSVM text (1-based ids)."""
    ids = indices.astype(np.int64) + 1
    with open(path, "w") as f:
        for lab, row in zip(labels.tolist(), ids.tolist()):
            f.write(f"{lab} " + " ".join(f"{i}:1" for i in row) + "\n")


def make_glm_files(work: str, rows: int, heldout: int, seed: int):
    """-> (train path, held-out path, train indices, train labels)."""
    idx, lab = glm_rows(rows + heldout, seed)
    train = os.path.join(work, "glm_train.libsvm")
    held = os.path.join(work, "glm_heldout.libsvm")
    write_libsvm(train, idx[:rows], lab[:rows])
    write_libsvm(held, idx[rows:], lab[rows:])
    return train, held, idx[:rows], lab[:rows]


def glm_reference(indices: np.ndarray, labels: np.ndarray, w: np.ndarray):
    """The L2 logistic objective and its gradient norm at ``w`` in plain
    numpy f64, independent of the program: (loss, |grad|, |grad at 0|)."""
    dim, k = w.shape[0], indices.shape[1]
    flat = indices.reshape(-1)

    def grad(v):
        m = v[indices].sum(axis=1)
        d = 1.0 / (1.0 + np.exp(-m)) - labels
        return np.bincount(flat, np.repeat(d, k), dim) + GLM_L2 * v, m

    w = np.asarray(w, np.float64)
    g, m = grad(w)
    loss = float(np.sum(np.logaddexp(0.0, m) - labels * m)
                 + 0.5 * GLM_L2 * w @ w)
    g0, _ = grad(np.zeros(dim))
    return loss, float(np.linalg.norm(g)), float(np.linalg.norm(g0))


def check_against_reference(indices, labels, w, loss, who: str) -> None:
    ref_loss, gnorm, gnorm0 = glm_reference(indices, labels, w)
    say(f"{who}: numpy f64 reference at the fitted w: loss={ref_loss:.6g} "
        f"(program says {loss:.6g}), |grad|={gnorm:.4g} "
        f"(at w=0: {gnorm0:.4g})")
    check(abs(loss - ref_loss) <= 1e-3 * ref_loss,
          f"{who}: loss {loss} but the reference computes {ref_loss}")
    check(gnorm <= 1e-2 * gnorm0,
          f"{who}: not a stationary point of the reference objective: "
          f"|grad|={gnorm} against {gnorm0} at w=0")


def make_game_files(work: str, rows: int, users: int, seed: int,
                    d_g: int = 24, d_u: int = 6, d_i: int = 4):
    """Mixed-effect data with two random effects (per-user + per-item) as
    TrainingExampleAvro, 80/20 train/held-out, plus the coordinate and
    feature-shard configs. Returns the held-out rows in serving's JSON
    shape, keyed by uid."""
    from photon_ml_tpu.io.data_reader import write_training_examples

    rng = np.random.default_rng(seed)
    items = max(users // 3, 2)
    w_fixed = rng.normal(size=d_g)
    U = rng.normal(size=(users, d_u)) * 1.5
    V = rng.normal(size=(items, d_i))
    uid = rng.integers(0, users, size=rows)
    iid = rng.integers(0, items, size=rows)
    X = {"g": rng.normal(size=(rows, d_g)), "u": rng.normal(size=(rows, d_u)),
         "i": rng.normal(size=(rows, d_i))}
    marg = (X["g"] @ w_fixed + np.einsum("ij,ij->i", X["u"], U[uid])
            + np.einsum("ij,ij->i", X["i"], V[iid]))
    y = (rng.random(rows) < 1 / (1 + np.exp(-marg))).astype(float)
    perm = rng.permutation(rows)
    cut = int(rows * 0.8)

    def named(i):
        return [(f"{p}{j}", "", float(v))
                for p in "gui" for j, v in enumerate(X[p][i])]

    def write(name, sel):
        path = os.path.join(work, name)
        write_training_examples(
            path, (named(i) for i in sel), y[sel],
            entity_ids={"userId": uid[sel], "itemId": iid[sel]},
            uids=[str(i) for i in sel])
        return path

    train = write("game_train.avro", perm[:cut])
    held = write("game_heldout.avro", perm[cut:])
    coords = [
        {"name": "fixed", "coordinate_type": "fixed",
         "feature_shard": "global", "reg_type": "l2", "reg_weight": 1.0,
         "max_iters": 50},
        {"name": "per-user", "coordinate_type": "random",
         "feature_shard": "user", "entity_column": "userId",
         "reg_type": "l2", "reg_weight": 1.0, "max_iters": 30},
        {"name": "per-item", "coordinate_type": "random",
         "feature_shard": "item", "entity_column": "itemId",
         "reg_type": "l2", "reg_weight": 1.0, "max_iters": 30},
    ]
    coords_path = os.path.join(work, "coords.json")
    shards_path = os.path.join(work, "shards.json")
    with open(coords_path, "w") as f:
        json.dump(coords, f)
    with open(shards_path, "w") as f:
        json.dump({"global": ["g"], "user": ["u"], "item": ["i"]}, f)
    requests = {
        str(i): {"uid": str(i),
                 "features": [{"name": n, "term": t, "value": v}
                              for n, t, v in named(i)],
                 "entityIds": {"userId": str(uid[i]), "itemId": str(iid[i])}}
        for i in perm[cut:cut + 512]}
    return train, held, coords_path, shards_path, requests


# -- bookkeeping ---------------------------------------------------------------

class CompileMeter:
    """Seconds spent in XLA compiles (or fetching them from the persistent
    cache) and cache hits, read off jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def run_phase(name: str, fn, meter: CompileMeter) -> bool:
    say(f"== phase {name}")
    s0, c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    ok = True
    try:
        fn()
    except BaseException as e:  # SystemExit from a driver counts too
        ok = False
        traceback.print_exc()
        say(f"phase {name} FAILED: {type(e).__name__}: {e}")
    s1, c1, h1 = meter.snapshot()
    say(f"phase {name}: {'ok' if ok else 'FAILED'} "
        f"wall_s={time.perf_counter() - t0:.1f} compile_s={s1 - s0:.1f} "
        f"compiles={c1 - c0} cache_hits={h1 - h0}")
    return ok


def log_events(output_dir: str, event: str):
    out = []
    with open(os.path.join(output_dir, "photon.log.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("event") == event:
                out.append(rec)
    return out


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
    except OSError:
        return 0


def chip_choices() -> dict:
    """What the program's "auto" settings resolve to on this backend, read
    off the same calls the fit makes (compiled text, not a flag)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.random_effect import resolve_re_optimizer
    from photon_ml_tpu.ops.pallas_kernels import multiply_prefix_sum
    from photon_ml_tpu.parallel.data_parallel import resolve_sparse_grad
    from photon_ml_tpu.types import table_gather

    x = jnp.ones((1 << 16,), jnp.float32)
    kernel = multiply_prefix_sum.lower(x, x).compile().as_text()
    idx = jnp.zeros((1 << 15,), jnp.int32)
    gather = jax.jit(table_gather).lower(x, idx).compile().as_text()
    return {
        "sparse_grad": resolve_sparse_grad("auto"),
        "prefix_kernel": ("compiled" if "tpu_custom_call" in kernel
                          else "interpreted"),
        "gather": "vector" if "slice_sizes={1,128}" in gather else "scalar",
        "re_solver": resolve_re_optimizer("auto", 8),
    }


# -- phases -------------------------------------------------------------------

def phase_glm(work: str, rows: int, heldout: int, seed: int) -> None:
    from photon_ml_tpu.cli.glm_driver import main as glm_main
    from photon_ml_tpu.io.model_io import load_game_model

    t0 = time.perf_counter()
    train, held, indices, labels = make_glm_files(work, rows, heldout, seed)
    say(f"glm data: LIBSVM (the Avro writer is a per-record Python loop), "
        f"dim={GLM_DIM} k={GLM_K} train_rows={rows} heldout_rows={heldout} "
        f"(rows cut from bench.py's 2^21) generated in "
        f"{time.perf_counter() - t0:.1f}s")
    out = os.path.join(work, "glm_out")
    rc = glm_main([
        "--train-data", train, "--validation-data", held,
        "--input-format", "libsvm", "--no-intercept",
        "--output-dir", out, "--task", "logistic_regression",
        "--optimizer", "lbfgs", "--reg-type", "l2",
        "--reg-weights", str(GLM_L2), "--max-iters", str(GLM_MAX_ITERS),
        "--evaluators", "auc", "--dtype", "float32",
    ])
    check(rc == 0, f"glm driver returned {rc}")
    check(os.path.exists(os.path.join(out, "best", "metadata.json")),
          "glm driver wrote no best/metadata.json")
    read = log_events(out, "data_read")[0]
    check(read["num_features"] == GLM_DIM and read["num_train"] == rows,
          f"glm driver read {read}")
    fit = log_events(out, "lambda_trained")[0]
    loss, loss0 = fit["loss"], rows * math.log(2.0)
    auc = fit["metrics"]["auc"]
    say(f"glm fit: loss={loss:.6g} (at w=0: {loss0:.6g}) "
        f"iterations={fit['iterations']} converged={fit['converged']} "
        f"heldout_auc={auc:.4f}")
    check(math.isfinite(loss) and loss < loss0,
          f"glm loss {loss} not below the loss at w=0 ({loss0})")
    check(auc >= GLM_AUC_MIN, f"glm held-out AUC {auc} < {GLM_AUC_MIN}")
    model = load_game_model(os.path.join(out, "best"))
    w = np.asarray(model.coordinates["global"].model.coefficients.means)
    check(w.shape == (GLM_DIM,) and np.isfinite(w).all(), "bad coefficients")
    check_against_reference(indices, labels, w, loss, "glm")


def phase_game(work: str, rows: int, users: int, seed: int) -> dict:
    from photon_ml_tpu import native
    from photon_ml_tpu.cli.game_scoring_driver import main as score_main
    from photon_ml_tpu.cli.game_training_driver import main as train_main

    # the C++ Avro decoder is built on demand and ingestion falls back to
    # the Python codec in silence when that fails: build it here, loudly
    native.build_library("avro_decoder")
    t0 = time.perf_counter()
    train, held, coords, shards, requests = make_game_files(
        work, rows, users, seed)
    say(f"game data: {rows} rows, {users} users, Avro generated in "
        f"{time.perf_counter() - t0:.1f}s")
    out = os.path.join(work, "game_out")
    t0 = time.perf_counter()
    rc = train_main([
        "--train-data", train, "--validation-data", held,
        "--output-dir", out, "--task", "logistic_regression",
        "--coordinates", coords, "--feature-shards", shards,
        "--n-iterations", "3",
    ])
    say(f"game training driver: rc={rc} wall_s={time.perf_counter() - t0:.1f}")
    check(rc == 0, f"game training driver returned {rc}")
    best = os.path.join(out, "best")
    check(os.path.exists(os.path.join(best, "metadata.json")),
          "game training driver wrote no best/metadata.json")
    scores_dir = os.path.join(work, "game_scores")
    t0 = time.perf_counter()
    rc = score_main(["--data", held, "--model-dir", best,
                     "--output-dir", scores_dir, "--evaluators", "auc"])
    say(f"game scoring driver: rc={rc} wall_s={time.perf_counter() - t0:.1f}")
    check(rc == 0, f"game scoring driver returned {rc}")
    auc = log_events(scores_dir, "evaluation")[0]["auc"]
    reader = ("native C++ decoder" if "avro_decoder" in native._LOADED
              else "Python codec")
    say(f"game: heldout_auc={auc:.4f} avro_reader={reader}")
    check(reader.startswith("native"), "Avro was read by the Python codec")
    check(auc >= GAME_AUC_MIN, f"game held-out AUC {auc} < {GAME_AUC_MIN}")
    return {"model_dir": best, "scores": os.path.join(scores_dir,
                                                      "scores.avro"),
            "requests": requests}


def _http(port: int, method: str, path: str, payload=None, timeout=120.0):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        body = None if payload is None else json.dumps(payload)
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"null")
    finally:
        conn.close()


def phase_serve(game: dict, log_dir: str) -> None:
    from photon_ml_tpu.cli.serving_driver import _free_port
    from photon_ml_tpu.cli.serving_driver import main as serve_main
    from photon_ml_tpu.io.avro import read_avro_file

    records, _ = read_avro_file(game["scores"])
    want = {r["uid"]: r["predictionScore"] for r in records}
    rows = list(game["requests"].values())
    batches = [rows[:1], rows[1:9], rows[9:73], rows[73:329]]
    port = _free_port("127.0.0.1")
    got: dict = {}
    state = {"error": None, "returned": False}
    lock = threading.Lock()

    def client():
        # host-only thread: waits for warm-up, sends the requests, then
        # asks the driver for its normal SIGTERM drain
        try:
            deadline = time.monotonic() + 900.0
            while True:
                if state["returned"]:
                    return
                try:
                    status, body = _http(port, "GET", "/healthz", timeout=5)
                    if status == 200 and body.get("status") == "ok":
                        break
                except OSError:
                    pass
                if time.monotonic() > deadline:
                    raise SmokeFailure("server not healthy after 900 s")
                time.sleep(0.5)
            for batch in batches:
                t0 = time.perf_counter()
                status, body = _http(port, "POST", "/score", {"rows": batch})
                if status != 200:
                    raise SmokeFailure(f"/score -> {status}: {body}")
                if body.get("degraded"):
                    raise SmokeFailure(f"degraded answer: {body['degraded']}")
                say(f"serve: {len(batch)} rows -> 200 in "
                    f"{(time.perf_counter() - t0) * 1e3:.1f} ms")
                for row, score in zip(batch, body["scores"]):
                    got[row["uid"]] = score
        except BaseException as e:
            state["error"] = e
        finally:
            with lock:
                if not state["returned"]:
                    os.kill(os.getpid(), signal.SIGTERM)

    t = threading.Thread(target=client, name="smoke-client", daemon=True)
    t.start()
    try:
        rc = serve_main([
            "--model-dir", game["model_dir"], "--host", "127.0.0.1",
            "--port", str(port), "--replicas", "1", "--server", "async",
            "--max-batch", "256", "--log-dir", log_dir,
        ])
    finally:
        with lock:
            state["returned"] = True
    t.join(30.0)
    check(not t.is_alive(), "serving client thread did not end")
    if state["error"] is not None:
        raise state["error"]
    check(rc == 0, f"serving driver returned {rc}")
    check(len(got) == sum(len(b) for b in batches), "scores missing")
    worst = max(abs(got[u] - want[u]) for u in got)
    say(f"serve: {len(got)} scores over HTTP, max |served - scoring "
        f"driver| = {worst:.3g} (limit {SERVE_TOL}); clean shutdown rc={rc}")
    check(worst <= SERVE_TOL, f"served scores differ by {worst}")


def phase_four_chips(work: str, rows: int, heldout: int, seed: int) -> None:
    """The data-parallel fit on a data=4 mesh against the same fit on a
    data=1 mesh, same data, one process."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.cli.glm_driver import _read
    from photon_ml_tpu.evaluation import get_evaluator
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.parallel.data_parallel import (
        build_csc,
        fit_distributed,
        resolve_sparse_grad,
    )
    from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch
    from photon_ml_tpu.types import SparseFeatures, make_batch

    train, held, indices, _ = make_glm_files(work, rows, heldout, seed)
    host, labels, offsets, weights, index_map, _ = _read(
        [train], "libsvm", None, False)
    vhost, vlabels, _, vweights, _, _ = _read([held], "libsvm", index_map,
                                              False)
    check(host.dim == GLM_DIM, f"dim {host.dim}")

    def device_batch(h, y, off=None, w=None):
        feats = SparseFeatures(jnp.asarray(h.indices),
                               jnp.asarray(h.values, jnp.float32), dim=h.dim)
        return make_batch(feats, y, off, w, dtype=jnp.float32)

    batch = device_batch(host, labels, offsets, weights)
    vbatch = device_batch(vhost, vlabels)
    sparse_grad = resolve_sparse_grad("auto", batch.features)
    say(f"four chips: sparse_grad={sparse_grad} rows={rows} dim={GLM_DIM}")
    # tolerance 0 turns the stopping tests off, so both fits run to the
    # solver's f32 floor: at the drivers' default tolerance each fit stops
    # ~1e-3 (relative, in w) short of the optimum, and two fits then differ
    # by that much whatever the mesh (1.8e-3 on the chip, 1.1e-3 on four
    # virtual CPU devices, PR 24) — which would hide a sharding fault of
    # the same size
    cfg = OptimizerConfig(max_iters=GLM_MAX_ITERS, tolerance=0.0)
    auc = get_evaluator("auc")
    results = {}
    for n in (4, 1):
        mesh = make_mesh({"data": n})
        obj = make_objective("logistic")
        t0 = time.perf_counter()
        sharded = shard_batch(batch, mesh)
        csc = (build_csc(obj, sharded, mesh)
               if sparse_grad.startswith("csc") else None)
        for name, tree in (("batch", sharded), ("csc", csc)):
            for leaf in jax.tree.leaves(tree):
                check(len(leaf.sharding.device_set) == n,
                      f"data={n}: a {name} array of shape {leaf.shape} lies "
                      f"on {len(leaf.sharding.device_set)} devices")
        res = fit_distributed(obj, sharded, mesh,
                              jnp.zeros((GLM_DIM,), jnp.float32), l2=GLM_L2,
                              optimizer="lbfgs", config=cfg,
                              precomputed_csc=csc)
        w = np.asarray(res.w)
        a = auc.evaluate(np.asarray(obj.margins(res.w, vbatch)), vlabels,
                         vweights)
        say(f"four chips: data={n} loss={float(res.value):.6g} "
            f"iterations={int(res.iterations)} heldout_auc={a:.4f} "
            f"wall_s={time.perf_counter() - t0:.1f} (compile included)")
        check_against_reference(indices, labels, w, float(res.value),
                                f"four chips: data={n}")
        results[n] = (w, a)
    (w4, a4), (w1, a1) = results[4], results[1]
    rel = float(np.linalg.norm(w4 - w1) / np.linalg.norm(w1))
    say(f"four chips: |w4 - w1| / |w1| = {rel:.3g}, "
        f"|auc4 - auc1| = {abs(a4 - a1):.3g}")
    check(np.isfinite(w4).all() and rel <= 1e-3,
          f"coefficients differ by {rel} relative")
    check(abs(a4 - a1) <= 1e-3, f"AUCs differ: {a4} vs {a1}")
    check(a4 >= GLM_AUC_MIN, f"held-out AUC {a4} < {GLM_AUC_MIN}")


# -- entry ------------------------------------------------------------------

def run(args, device: dict) -> bool:
    import jax

    from photon_ml_tpu.utils import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    device.update(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices))
    say(f"== phase device: {device}")
    if device["platform"] != "tpu":
        say("no TPU: this script checks the program on the chip and has no "
            "CPU fallback")
        return False
    if len(devices) != args.chips:
        say(f"--chips {args.chips} but jax.devices() has {len(devices)}")
        return False
    say(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries)")
    meter = CompileMeter()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            return run_phase("four_chips", lambda: phase_four_chips(
                work, GLM_ROWS, GLM_HELDOUT, args.seed), meter)
        choices = chip_choices()
        say(f"resolved on this chip: {choices}")
        want = {"sparse_grad": "csc_pallas", "prefix_kernel": "compiled",
                "gather": "vector", "re_solver": "newton"}
        ok = choices == want
        if not ok:
            say(f"expected {want}")
        ok &= run_phase("glm", lambda: phase_glm(
            work, GLM_ROWS, GLM_HELDOUT, args.seed), meter)
        game: dict = {}
        game_ok = run_phase("game", lambda: game.update(phase_game(
            work, GAME_ROWS, GAME_USERS, args.seed)), meter)
        ok &= game_ok
        if game_ok:
            ok &= run_phase("serve", lambda: phase_serve(
                game, os.path.join(work, "serve_log")), meter)
        else:
            say("phase serve: FAILED (no model to serve)")
        return bool(ok)
    finally:
        shutil.rmtree(work, ignore_errors=True)
        s, c, h = meter.snapshot()
        say(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries) "
            f"total compile_s={s:.1f} compiles={c} cache_hits={h}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=[1, 4], default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    device = {"platform": None, "kind": None, "count": 0}
    t0 = time.perf_counter()
    try:
        ok = run(args, device)
    except BaseException:
        traceback.print_exc()
        ok = False
    say(f"chip_smoke: {'passed' if ok else 'FAILED'} in "
        f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
