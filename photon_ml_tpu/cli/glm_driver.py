"""Classic (non-GAME) GLM training driver — the staged pipeline.

Equivalent of the reference's legacy ``com.linkedin.photon.ml.Driver``
(SURVEY.md §3.3, marked ``(?)``; reference mount empty): a fixed sequence of
stages — validate → summarize/normalize → train one model per regularization
weight with **warm start** across the lambda grid → validate + select best →
diagnostics — for a single fixed-effect GLM, no random effects. The GAME
driver supersedes this for mixed-effect models; this driver remains the
shortest path for plain sparse GLMs (the a1a / Criteo baseline configs,
BASELINE.md #1–#3).

TPU-native shape: each lambda's fit is one jitted device computation
(`fit_distributed`: sharded batch + psum — SURVEY.md §4.2); the lambda loop
reuses the same compiled program because the regularization weight is a
traced argument.

Usage:
    python -m photon_ml_tpu.cli.glm_driver \
        --train-data a1a --input-format libsvm --task logistic_regression \
        --reg-weights 0.1 1.0 10.0 --optimizer lbfgs --output-dir out/
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.evaluation import get_evaluator
from photon_ml_tpu.evaluation.evaluators import TASK_DEFAULT_EVALUATOR
from photon_ml_tpu.game.data import HostSparse
from photon_ml_tpu.io.avro import iter_avro_records
from photon_ml_tpu.io.data_reader import read_training_examples
from photon_ml_tpu.io.index_map import IndexMap, build_index_map
from photon_ml_tpu.io.libsvm import read_libsvm
from photon_ml_tpu.io.model_io import save_game_model
from photon_ml_tpu.io.validators import validate_training_data
from photon_ml_tpu.models import (
    Coefficients,
    FixedEffectModel,
    GameModel,
    GeneralizedLinearModel,
)
from photon_ml_tpu.ops.losses import TASK_TO_LOSS
from photon_ml_tpu.ops.normalization import (
    NormalizationType,
    build_normalization_context,
)
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.ops.statistics import summarize_features
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.parallel.data_parallel import fit_distributed
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import LabeledBatch, SparseFeatures, make_batch
from photon_ml_tpu.utils import (PhotonLogger, Timed,
                                 configure_compile_cache, is_device_loss,
                                 resolve_dtype)


def _tol_schedule(value: str):
    from photon_ml_tpu.optimize import parse_tolerance_schedule

    try:
        return parse_tolerance_schedule(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="Classic GLM training driver "
                                            "(staged pipeline, TPU-native)")
    p.add_argument("--train-data", required=True, nargs="+")
    p.add_argument("--validation-data", nargs="+", default=None)
    p.add_argument("--input-format", default="avro", choices=["avro", "libsvm"])
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task", default="logistic_regression",
                   choices=sorted(TASK_TO_LOSS) + sorted(set(TASK_TO_LOSS.values())))
    p.add_argument("--optimizer", default="lbfgs",
                   choices=["lbfgs", "owlqn", "tron"])
    p.add_argument("--reg-type", default="l2",
                   choices=["none", "l1", "l2", "elastic_net"])
    p.add_argument("--reg-weights", type=float, nargs="+", default=[0.0],
                   help="lambda grid; trained in order with warm start")
    p.add_argument("--elastic-net-alpha", type=float, default=0.5)
    p.add_argument("--max-iters", type=int, default=100)
    p.add_argument("--tolerance", type=float, default=1e-7)
    p.add_argument("--solver-tol-schedule", type=_tol_schedule, default=None,
                   metavar="START:DECAY",
                   help="inexact path-following over the lambda grid: the "
                        "i-th lambda solves to max(--tolerance, START * "
                        "DECAY^i) — early grid points only warm-start the "
                        "chain, so a loose solve there buys wall-clock "
                        "without moving the tight final fits (e.g. "
                        "1e-3:0.1; 'off' disables)")
    p.add_argument("--path-screen", default="off",
                   choices=["off", "strong", "safe"],
                   help="pathwise screening over the lambda grid "
                        "(optimize/path.py, docs/path.md): walk "
                        "--reg-weights in decreasing order, freeze "
                        "features the sequential strong/safe rule screens "
                        "out, solve the restricted problem, and KKT-"
                        "certify against the full gradient (violators "
                        "re-enter and the solve repeats). Composes with "
                        "warm start, --solver-tol-schedule and "
                        "--auto-resume; requires an L1 component "
                        "(l1/elastic_net) to bite and refuses "
                        "--normalization")
    p.add_argument("--path-kkt-tol", type=float, default=1e-6,
                   help="relative slack of the screened-coordinate KKT "
                        "certification test (ops.regularization."
                        "kkt_slack)")
    p.add_argument("--path-max-kkt-rounds", type=int, default=5,
                   help="restricted-solve repair rounds per lambda before "
                        "falling back to a full-width solve")
    p.add_argument("--path-min-bucket", type=int, default=64,
                   help="floor of the power-of-two restricted-width "
                        "bucket ladder")
    p.add_argument("--normalization", default="none",
                   choices=[t.value for t in NormalizationType])
    p.add_argument("--add-intercept", action="store_true", default=True)
    p.add_argument("--no-intercept", dest="add_intercept", action="store_false")
    p.add_argument("--index-map", default=None,
                   help="prebuilt index map (avro input only)")
    p.add_argument("--hash-dim", type=int, default=None,
                   help="feature-hash into this width instead of building an "
                        "index map (avro input only)")
    p.add_argument("--min-feature-count", type=int, default=1)
    p.add_argument("--evaluators", nargs="*", default=None)
    p.add_argument("--validate-data", action="store_true", default=True,
                   help="run DataValidators-style checks before training")
    p.add_argument("--no-validate-data", dest="validate_data",
                   action="store_false")
    p.add_argument("--auto-resume", action="store_true",
                   help="resume a lambda grid that died on device loss "
                        "(RESUME_GLM.npz marker / exit code 75)")
    p.add_argument("--max-rank-failures", type=int, default=0,
                   help="in-job recovery: retry a lambda fit that died in "
                        "a TRANSIENT coordinated abort (every rank alive, "
                        "generic local error) up to this many times, with "
                        "jittered backoff and a re-aligning barrier. GLM "
                        "coefficients are replicated, so there is nothing "
                        "to redistribute: rank loss, device loss and data "
                        "errors still escalate to the --auto-resume "
                        "whole-job path (parallel/recovery.py)")
    p.add_argument("--recovery-snapshot-every", type=int, default=1,
                   help="accepted for CLI parity with photon-game-train; "
                        "the GLM grid's recovery unit is one LAMBDA (every "
                        "finished lambda is already persisted to the "
                        "resume marker), so this knob has no finer "
                        "granularity to select here")
    p.add_argument("--compute-variances", action="store_true",
                   help="diagonal-inverse-Hessian coefficient variances")
    p.add_argument("--summarize-features", action="store_true")
    p.add_argument("--diagnostics", action="store_true",
                   help="write diagnostics.json for the best model: Hosmer-"
                        "Lemeshow fit test (binary), feature importance, "
                        "optional bootstrap CIs")
    p.add_argument("--bootstrap-replicates", type=int, default=0,
                   help="bootstrap refits for coefficient CIs (vmapped into "
                        "one batched fit; 0 disables)")
    p.add_argument("--streaming", action="store_true",
                   help="larger-than-HBM mode: keep the training set in host "
                        "RAM and stream fixed-shape chunks through the "
                        "device each optimizer pass")
    p.add_argument("--out-of-core", action="store_true",
                   help="larger-than-host-RAM mode (implies --streaming): "
                        "never materialize the training set — each optimizer "
                        "pass re-decodes Avro block waves from disk on a "
                        "background thread (io/stream_source.py). Requires "
                        "--input-format avro and a pinned feature space "
                        "(--hash-dim or --index-map); full-data validation/"
                        "summarization/normalization are unavailable (no "
                        "resident data to scan)")
    p.add_argument("--pad-nnz", type=int, default=None,
                   help="fixed per-row feature width incl. intercept for "
                        "--out-of-core (default: one measuring decode pass)")
    p.add_argument("--chunk-rows", type=int, default=1 << 16,
                   help="rows per streamed chunk (--streaming)")
    p.add_argument("--chunk-cache-dir", default=None,
                   help="with --out-of-core: decode-once packed chunk "
                        "cache directory (io/chunk_cache.py) — the first "
                        "optimizer pass spills decoded chunks into packed "
                        "memmaps there, every later pass streams them "
                        "back decode-free. Invalidated automatically when "
                        "the source files, chunk geometry, or index map "
                        "change; multi-process runs need per-process dirs")
    p.add_argument("--chunk-cache-gb", type=float, default=None,
                   help="disk budget for --chunk-cache-dir; a dataset "
                        "that doesn't fit falls through to re-decode "
                        "with a logged warning (default: unbounded)")
    p.add_argument("--prefetch-depth", type=int, default=None,
                   help="streamed transfer-ring depth: how many chunks "
                        "the transfer thread stages on device ahead of "
                        "compute (default 2 / PHOTON_PREFETCH_DEPTH; 0 = "
                        "synchronous)")
    p.add_argument("--dtype", default="float32", choices=["float32", "float64"])
    p.add_argument("--coordinator-address", default=None,
                   help="multi-host: coordinator host:port for "
                        "jax.distributed.initialize (every process runs this "
                        "driver with the same args)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--profile-dir", default=None,
                   help="capture a JAX profiler trace of training here")
    p.add_argument("--trace-dir", default=None,
                   help="write photon-trace span files here (one "
                        "trace-rankN.json per process; merge with "
                        "`photon-trace merge`; docs/observability.md)")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="fraction of traces recorded under --trace-dir")
    return p


def _read(paths, fmt, index_map: Optional[IndexMap], add_intercept):
    """-> (HostSparse, labels, offsets, weights, index_map, intercept_index).
    Host-side only; device conversion happens after validation."""
    if fmt == "libsvm":
        # read raw (no intercept) so multiple files share one feature space,
        # then append the intercept column at the common dim
        parts = [read_libsvm(p) for p in paths]
        # an index_map (from the training pass) pins the feature space, so
        # validation files line up with the trained model: features beyond it
        # are dropped, missing ones stay implicit zeros
        if index_map is not None:
            base_dim = index_map.size - (
                1 if index_map.intercept_index >= 0 else 0
            )
            for sp, _, _ in parts:
                drop = sp.indices >= base_dim
                sp.indices[drop] = 0
                sp.values[drop] = 0.0
        else:
            base_dim = max(sp.dim for sp, _, _ in parts)
        intercept = base_dim if add_intercept else -1
        dim = base_dim + (1 if add_intercept else 0)
        k = max(sp.values.shape[1] for sp, _, _ in parts) + (
            1 if add_intercept else 0
        )
        n = sum(sp.num_rows for sp, _, _ in parts)
        indices = np.zeros((n, k), np.int32)
        values = np.zeros((n, k))
        at = 0
        for sp, _, _ in parts:
            m, kk = sp.values.shape
            indices[at:at + m, :kk] = sp.indices
            values[at:at + m, :kk] = sp.values
            if add_intercept:
                indices[at:at + m, kk] = intercept
                values[at:at + m, kk] = 1.0
            at += m
        labels = np.concatenate([lab for _, lab, _ in parts])
        feats = HostSparse(indices, values, dim)
        if index_map is None:
            entries = {f"f{i}": i for i in range(base_dim)}
            if intercept >= 0:
                entries["(INTERCEPT)"] = intercept
            index_map = IndexMap(entries)
        return feats, labels, np.zeros(n), np.ones(n), index_map, intercept
    feats, labels, offsets, weights, _, _ = read_training_examples(
        paths, index_map
    )
    return (feats["global"], labels, offsets, weights, index_map,
            index_map.intercept_index)


def main(argv: Sequence[str] | None = None) -> int:
    configure_compile_cache()
    args = build_arg_parser().parse_args(argv)
    from photon_ml_tpu.obs import logging as obs_logging
    from photon_ml_tpu.obs import trace as obs_trace

    obs_logging.configure()
    if args.trace_dir:
        started = obs_trace.start(args.trace_dir, sample=args.trace_sample)
    else:
        started = obs_trace.maybe_start_from_env()
    try:
        return _run(args)
    finally:
        # every exit path exports the trace files; only stop a tracer
        # this invocation started (simulated-harness ranks share one)
        if started is not None:
            obs_trace.stop()


def _run(args) -> int:
    from photon_ml_tpu.parallel import fault_injection, resilience
    from photon_ml_tpu.parallel.multihost import initialize_multihost, runtime_info

    distributed = initialize_multihost(args.coordinator_address,
                                       args.num_processes, args.process_id)
    dtype = resolve_dtype(args.dtype)
    task = TASK_TO_LOSS.get(args.task, args.task)
    os.makedirs(args.output_dir, exist_ok=True)
    logger = PhotonLogger(os.path.join(args.output_dir, "photon.log.jsonl"))
    logger.log("driver_start", driver="glm", args=vars(args),
               distributed=distributed, **runtime_info())

    reg = RegularizationContext(args.reg_type, alpha=args.elastic_net_alpha)
    optimizer = args.optimizer
    if reg.needs_owlqn and optimizer != "owlqn":
        logger.log("optimizer_override", requested=optimizer, used="owlqn",
                   reason=f"reg_type={args.reg_type} needs OWL-QN")
        optimizer = "owlqn"

    if args.path_screen != "off" \
            and NormalizationType(args.normalization) != NormalizationType.NONE:
        raise SystemExit(
            "--path-screen does not compose with --normalization: the "
            "virtual shift couples every column through the margin "
            "adjustment, so a frozen column would still move the margins "
            "(optimize/path.py). Normalize the data on disk or drop one "
            "of the flags")

    out_of_core = args.out_of_core
    if args.chunk_cache_dir and not out_of_core:
        raise SystemExit("--chunk-cache-dir requires --out-of-core (the "
                         "in-RAM streaming path never re-decodes)")
    if args.chunk_cache_gb is not None and not args.chunk_cache_dir:
        raise SystemExit("--chunk-cache-gb requires --chunk-cache-dir")
    if out_of_core:
        if args.input_format != "avro":
            raise SystemExit("--out-of-core requires --input-format avro")
        if not (args.hash_dim or args.index_map):
            raise SystemExit(
                "--out-of-core needs a pinned feature space (--hash-dim or "
                "--index-map): building an index map would scan the full "
                "dataset — run the feature indexing driver first")
        if args.summarize_features or NormalizationType(args.normalization) != NormalizationType.NONE:
            raise SystemExit("--out-of-core cannot summarize/normalize: "
                             "no resident data to scan")

    # -- stage: read + index -------------------------------------------------
    with Timed(logger, "read_train_data"):
        index_map = None
        if args.input_format == "avro":
            if args.hash_dim:
                from photon_ml_tpu.io.hashing import HashingIndexMap

                index_map = HashingIndexMap(args.hash_dim,
                                            add_intercept=args.add_intercept)
            elif args.index_map:
                from photon_ml_tpu.io.paldb import load_index_map

                index_map = load_index_map(args.index_map)
            else:
                index_map = build_index_map(
                    iter_avro_records(args.train_data),
                    add_intercept=args.add_intercept,
                    min_count=args.min_feature_count,
                )
        if out_of_core:
            from photon_ml_tpu.io.stream_source import AvroChunkSource

            n_local_dev = max(len(jax.local_devices()), 1)
            ooc_chunk_rows = -(-args.chunk_rows // n_local_dev) * n_local_dev
            src = AvroChunkSource(
                args.train_data, index_map, chunk_rows=ooc_chunk_rows,
                pad_nnz=args.pad_nnz, dtype=resolve_dtype(args.dtype),
                process_part=((jax.process_index(), jax.process_count())
                              if distributed else None))
            if args.chunk_cache_dir:
                from photon_ml_tpu.io.chunk_cache import ChunkCacheSource

                src = ChunkCacheSource(
                    src, args.chunk_cache_dir,
                    max_bytes=(None if args.chunk_cache_gb is None
                               else int(args.chunk_cache_gb * 1e9)))
            host_feats = labels = offsets = weights = None
            intercept_index = index_map.intercept_index
        else:
            (host_feats, labels, offsets, weights, index_map,
             intercept_index) = _read(
                args.train_data, args.input_format, index_map,
                args.add_intercept
            )
    validation = None
    if args.validation_data:
        with Timed(logger, "read_validation_data"):
            vhost, vlabels, voffsets, vweights, _, _ = _read(
                args.validation_data, args.input_format, index_map,
                args.add_intercept,
            )
            validation = (vhost, vlabels, voffsets, vweights)
    logger.log("data_read",
               num_train=(src.rows if out_of_core else int(labels.shape[0])),
               num_validation=0 if validation is None else int(vlabels.shape[0]),
               num_features=(index_map.size if out_of_core
                             else host_feats.dim))

    # -- stage: validate (on host, before any device transfer) ---------------
    if args.validate_data:
        with Timed(logger, "validate_data"):
            if out_of_core:
                # no resident training data to scan: structural validation
                # happens per decoded chunk (the source raises on unlabeled
                # / malformed records); only validation data is checked here
                logger.log("validate_skipped_out_of_core")
            else:
                validate_training_data(host_feats, labels, offsets, weights,
                                       task=task)
            if validation is not None:
                validate_training_data(vhost, vlabels, voffsets, vweights,
                                       task=task)

    # -- stage: summarize + normalization ------------------------------------
    streaming = args.streaming or out_of_core
    dim = index_map.size if out_of_core else host_feats.dim
    if out_of_core:
        chunks = src
        batch = None
    elif streaming:
        from photon_ml_tpu.parallel.multihost import process_span
        from photon_ml_tpu.parallel.streaming import make_host_chunks

        # training set stays in host RAM; only fixed-shape chunks ever
        # touch the device. Distributed: each process streams only its own
        # contiguous row span (the reference's input-split assignment); the
        # per-chunk partials then psum over the full mesh.
        span = process_span(len(labels)) if distributed else (0, len(labels))
        sl = slice(*span)
        from photon_ml_tpu.game.data import HostSparse

        local_feats = HostSparse(np.asarray(host_feats.indices)[sl],
                                 np.asarray(host_feats.values)[sl],
                                 host_feats.dim)
        n_local_dev = max(len(jax.local_devices()), 1)
        chunk_rows = -(-args.chunk_rows // n_local_dev) * n_local_dev
        chunks, _ = make_host_chunks(
            local_feats, np.asarray(labels)[sl], np.asarray(offsets)[sl],
            np.asarray(weights)[sl], chunk_rows=chunk_rows)
        batch = LabeledBatch(host_feats, labels, offsets, weights)
        feats = None
    else:
        feats = SparseFeatures(jnp.asarray(host_feats.indices),
                               jnp.asarray(host_feats.values, dtype),
                               dim=dim)
        batch = make_batch(feats, labels, offsets, weights, dtype=dtype)
    validation_batch = None
    if validation is not None:
        vfeats = SparseFeatures(jnp.asarray(vhost.indices),
                                jnp.asarray(vhost.values, dtype),
                                dim=vhost.dim)
        validation_batch = make_batch(vfeats, vlabels, voffsets, vweights,
                                      dtype=dtype)
    norm_type = NormalizationType(args.normalization)
    normalization = None
    if norm_type != NormalizationType.NONE or args.summarize_features:
        with Timed(logger, "feature_summarization"):
            summary = summarize_features(batch)
            if args.summarize_features:
                from photon_ml_tpu.cli.game_training_driver import _write_summary

                _write_summary(args.output_dir, summary, index_map)
            if norm_type != NormalizationType.NONE:
                normalization = build_normalization_context(
                    norm_type, summary, intercept_index=intercept_index
                )

    objective = make_objective(task, normalization=normalization,
                               intercept_index=intercept_index)
    mesh = make_mesh()
    # streamed chunks shard over THIS process's devices only; the global
    # mesh is for the in-memory fit_distributed path
    stream_mesh = (mesh if not distributed
                   else make_mesh({"data": len(jax.local_devices())},
                                  devices=jax.local_devices()))
    opt_config = OptimizerConfig(max_iters=args.max_iters,
                                 tolerance=args.tolerance)

    evaluators = args.evaluators
    if evaluators is None:
        evaluators = [TASK_DEFAULT_EVALUATOR[task]] if validation is not None else []

    # -- stage: train over the lambda grid with warm start -------------------
    results = []
    w = jnp.zeros((dim,), dtype)
    from photon_ml_tpu.obs.trace import profile

    # Device-loss recovery over the lambda grid (same contract as the
    # GAME driver's RESUME marker, but lambda-granular: every finished
    # lambda's host-side result is persisted, so the rerun replays them
    # and resumes the warm-start chain at the first unfinished lambda).
    is_lead = (not distributed) or jax.process_index() == 0
    # Unified marker lifecycle (parallel/resilience.ResumeManager): atomic
    # writes, kept until the grid completes, and a validation-input
    # fingerprint — restored per-lambda metrics were computed on the
    # crashed run's validation dataset, so a rerun pointed at different
    # --validation-data must refuse resume instead of mixing metrics from
    # two datasets when selecting the best lambda.
    resume = resilience.ResumeManager(
        os.path.join(args.output_dir, "RESUME_GLM.npz"),
        fingerprint={
            "train_data": sorted(args.train_data),
            "validation_data": (sorted(args.validation_data)
                                if args.validation_data else None),
            "validation_rows": (None if validation is None
                                else int(vlabels.shape[0])),
            # a resumed path must re-screen the tail exactly as the
            # crashed run would have: refuse to resume across a change
            # of screening rule
            "path_screen": args.path_screen,
        },
        is_lead=is_lead)
    resume_path = resume.path
    if args.auto_resume and resume.exists():
        from types import SimpleNamespace

        # driver-specific compatibility checks run FIRST (their error
        # messages name the actual mismatch); the input fingerprint is
        # verified after, below
        saved = resume.load(verify=False)
        saved_lams = [e["lam"] for e in saved["entries"]]
        if saved_lams != list(args.reg_weights[: len(saved_lams)]):
            raise ValueError(
                f"RESUME_GLM.npz holds lambdas {saved_lams} which are not a "
                f"prefix of --reg-weights {list(args.reg_weights)}; refusing "
                "to mix grids — rerun with the original grid or delete the "
                "marker")
        if validation is not None and evaluators and any(
                evaluators[0] not in (e["metrics"] or {})
                for e in saved["entries"]):
            raise ValueError(
                "RESUME_GLM.npz entries lack the current evaluator "
                f"{evaluators[0]!r} (the crashed run had different "
                "validation settings); rerun with the original settings or "
                "delete the marker")
        resume.verify(saved)  # refuse changed train/validation inputs
        for e in saved["entries"]:
            res_like = SimpleNamespace(**e["res"])
            res_like.w = jnp.asarray(res_like.w, dtype)
            results.append((e["lam"], res_like, e["metrics"], e["variances"]))
        w = jnp.asarray(saved["last_w"], dtype)
        # the marker is consumed only after the grid COMPLETES (below): a
        # second failure of any kind must not discard the progress
        logger.log("auto_resume", completed_lambdas=len(results))

    def _persist_resume(err):
        entries = [{
            "lam": lam,
            "res": {"w": np.asarray(res.w),  # native dtype: a resumed
                    # f64 run must reproduce the uninterrupted one
                    "value": float(res.value),
                    "grad_norm": float(res.grad_norm),
                    "iterations": int(res.iterations),
                    "converged": bool(res.converged),
                    "solver_tolerance": getattr(res, "solver_tolerance",
                                                None),
                    "screened_dim": getattr(res, "screened_dim", None),
                    "loss_history": np.asarray(res.loss_history)},
            "metrics": metrics_,
            "variances": (None if variances_ is None
                          else np.asarray(variances_)),
        } for lam, res, metrics_, variances_ in results]
        resume.save({
            "entries": entries,
            "last_w": (np.asarray(results[-1][1].w)
                       if results else np.zeros((dim,))),
            "error": str(err).split("\n")[0],
        })

    # the per-dataset column sort behind the csc gradient paths is paid
    # once for the whole lambda grid, not per fit
    grid_csc = None
    if not streaming:
        from photon_ml_tpu.parallel.data_parallel import (
            build_csc, resolve_sparse_grad, uses_csc,
        )

        if uses_csc(resolve_sparse_grad("auto", batch.features)):
            grid_csc = build_csc(objective, batch, mesh)

    path_solver = None
    if args.path_screen != "off":
        from photon_ml_tpu.optimize import PathConfig, PathSolver

        pcfg = PathConfig(screen=args.path_screen,
                          kkt_tol=args.path_kkt_tol,
                          max_kkt_rounds=args.path_max_kkt_rounds,
                          min_bucket=args.path_min_bucket)
        if streaming:
            # out-of-core: the restricted passes stream the SAME chunk
            # sequence (the PR-4 chunk cache underneath makes the whole
            # path one decode of the data)
            path_solver = PathSolver(
                objective, reg, chunks=chunks, dim=dim, mesh=stream_mesh,
                optimizer=optimizer, config=opt_config, path_config=pcfg,
                dtype=dtype, prefetch_depth=args.prefetch_depth)
        else:
            path_solver = PathSolver(
                objective, reg, batch=batch, mesh=mesh,
                optimizer=optimizer, config=opt_config, path_config=pcfg,
                dtype=dtype, precomputed_csc=grid_csc)
        # lambda-granular resume: replayed solutions seed warm/screening
        # states (gradients recomputed lazily), so the resumed tail's
        # candidate sets match the uninterrupted run's
        for lam_done, res_done, _m, _v in results:
            path_solver.seed_state(lam_done, np.asarray(res_done.w))

    try:
        with Timed(logger, "training"), profile(args.profile_dir):
            start_idx = len(results)
            for li, lam in enumerate(args.reg_weights[start_idx:],
                                     start=start_idx):
                # per-lambda injection point: kill-and-rerun tests drive
                # the device-loss resume path through here without
                # monkeypatching the fit internals
                fault_injection.check("glm.lambda")
                run_config = opt_config
                if args.solver_tol_schedule is not None:
                    import dataclasses as _dc

                    run_config = _dc.replace(
                        opt_config,
                        tolerance=args.solver_tol_schedule.at(
                            li, args.tolerance))
                path_stats_box = [None]

                def _fit_lambda(lam=lam, run_config=run_config):
                    if path_solver is not None:
                        res_, pstats = path_solver.solve(
                            lam, tolerance=run_config.tolerance)
                        path_stats_box[0] = pstats
                        return res_
                    if streaming:
                        from photon_ml_tpu.parallel.streaming import (
                            fit_streaming,
                        )

                        # distributed: chunks hold this process's span only
                        # and the partials allgather-reduce across
                        # processes; chunk sharding uses the process-LOCAL
                        # mesh so per-process partials stay local sums
                        # while all local chips work each pass
                        return fit_streaming(
                            objective, chunks, dim, w0=w,
                            l2=reg.l2_weight(lam), l1=reg.l1_weight(lam),
                            optimizer=optimizer, config=run_config,
                            dtype=dtype, mesh=stream_mesh,
                            prefetch_depth=args.prefetch_depth,
                        )
                    return fit_distributed(
                        objective, batch, mesh, w,
                        l2=reg.l2_weight(lam), l1=reg.l1_weight(lam),
                        optimizer=optimizer, config=run_config,
                        precomputed_csc=grid_csc,
                    )

                if args.max_rank_failures > 0:
                    # bounded collective rollback-retry: a transient
                    # coordinated abort (every rank alive) re-runs this
                    # lambda from the same warm start instead of killing
                    # the whole grid; anything else propagates to the
                    # device-loss/resume handling below
                    from photon_ml_tpu.parallel.recovery import (
                        retry_collective,
                    )

                    res = retry_collective(
                        _fit_lambda, max_retries=args.max_rank_failures,
                        tag=f"glm.lambda_retry:{li}")
                else:
                    res = _fit_lambda()
                # every fit records the tolerance it solved to and the
                # width it solved over (full dim when unscreened), so the
                # lambda log and resume marker always carry both
                if res.solver_tolerance is None:
                    res = res._replace(
                        solver_tolerance=float(run_config.tolerance))
                if res.screened_dim is None:
                    res = res._replace(screened_dim=int(dim))
                w = res.w  # warm start the next lambda
                diag = {
                    "reg_weight": lam,
                    "solver_tolerance": float(res.solver_tolerance),
                    "screened_dim": int(res.screened_dim),
                    "loss": float(res.value),
                    "grad_norm": float(res.grad_norm),
                    "iterations": int(res.iterations),
                    "converged": bool(res.converged),
                    "loss_history": [
                        float(v) for v in np.asarray(res.loss_history)
                        if np.isfinite(v)
                    ],
                }
                if res.gather_products is not None:
                    # what the passes cost: the X v and X^T d products the
                    # optimizer ran (a line search or CG step adds to
                    # these, not to `iterations`)
                    diag["gather_products"] = int(res.gather_products)
                    diag["transpose_products"] = int(res.transpose_products)
                if res.stream_stats is not None:
                    # streamed fits: decode-wait / transfer / compute-stall
                    # seconds for this lambda's whole pass sequence
                    diag["stream"] = res.stream_stats
                if path_stats_box[0] is not None:
                    diag["path"] = path_stats_box[0].as_dict()
                metrics = {}
                if validation_batch is not None and evaluators:
                    scores = np.asarray(objective.margins(res.w, validation_batch))
                    for name in evaluators:
                        metrics[name] = get_evaluator(name).evaluate(
                            scores, vlabels, vweights
                        )
                    diag["metrics"] = metrics
                variances = None
                if args.compute_variances:
                    if streaming:
                        from photon_ml_tpu.parallel.streaming import (
                            streaming_coefficient_variances,
                        )

                        variances = streaming_coefficient_variances(
                            objective, chunks, dim, res.w,
                            l2=reg.l2_weight(lam), dtype=dtype, mesh=stream_mesh,
                            prefetch_depth=args.prefetch_depth,
                        )
                    else:
                        variances = objective.coefficient_variances(
                            res.w, batch, reg.l2_weight(lam)
                        )
                results.append((lam, res, metrics, variances))
                logger.log("lambda_trained", **diag)

    except Exception as e:
        if not is_device_loss(e):
            raise
        _persist_resume(e)
        logger.log("device_lost", error=str(e).split("\n")[0],
                   completed_lambdas=len(results))
        logger.close()
        print(f"device lost; {len(results)} finished lambdas persisted to "
              f"{resume_path} (rerun with --auto-resume)", file=sys.stderr)
        return 75

    try:
        # -- stage: validate + select best ---------------------------------------
        best_i = 0
        if validation is not None and evaluators:
            ev = get_evaluator(evaluators[0])
            for i in range(1, len(results)):
                if ev.better(results[i][2][evaluators[0]],
                             results[best_i][2][evaluators[0]]):
                    best_i = i

        if args.diagnostics:
            from photon_ml_tpu import diagnostics as diag

            lam_best, res_best, _, _ = results[best_i]
            report = {"reg_weight": lam_best}
            inverse = index_map.inverse()
            summary_std = None
            if norm_type != NormalizationType.NONE or args.summarize_features:
                summary_std = np.zeros(dim)
                summary_std[:summary.dim] = summary.std
            imp = diag.feature_importance(np.asarray(res_best.w), summary_std,
                                          top_k=50)
            report["feature_importance"] = [
                {"feature": inverse.get(int(i), str(int(i))),
                 "score": float(s)}
                for i, s in zip(imp["index"], imp["score"])
            ]
            if validation_batch is not None and task in ("logistic",
                                                         "smoothed_hinge"):
                probs = np.asarray(
                    objective.loss.mean(
                        objective.margins(res_best.w, validation_batch)
                    )
                )
                report["hosmer_lemeshow"] = diag.hosmer_lemeshow(probs, vlabels)
            if args.bootstrap_replicates > 0 and not streaming:
                with Timed(logger, "bootstrap"):
                    boot = diag.bootstrap_coefficients(
                        objective, batch, res_best.w,
                        l2=reg.l2_weight(lam_best),
                        n_replicates=args.bootstrap_replicates,
                    )
                report["bootstrap"] = {
                    "replicates": args.bootstrap_replicates,
                    "std": boot["std"].tolist(),
                    "lower": boot["lower"].tolist(),
                    "upper": boot["upper"].tolist(),
                }
            with open(os.path.join(args.output_dir, "diagnostics.json"), "w") as f:
                json.dump(report, f, indent=2)
            logger.log("diagnostics_written",
                       hosmer_lemeshow=report.get("hosmer_lemeshow"))

        # -- stage: diagnostics + model output ------------------------------------
        with Timed(logger, "save_models"):
            for i, (lam, res, metrics, variances) in enumerate(results):
                model = GameModel(
                    {"global": FixedEffectModel(
                        GeneralizedLinearModel(
                            Coefficients(res.w, variances), task=task
                        )
                    )},
                    task=task,
                )
                out = os.path.join(
                    args.output_dir,
                    "best" if i == best_i else os.path.join("all", f"lambda-{lam:g}"),
                )
                save_game_model(model, out, index_map)
                if i == best_i and len(results) > 1:
                    save_game_model(
                        model, os.path.join(args.output_dir, "all", f"lambda-{lam:g}"),
                        index_map,
                    )
    except Exception as e:
        if not is_device_loss(e):
            raise
        _persist_resume(e)
        logger.log("device_lost", error=str(e).split("\n")[0],
                   completed_lambdas=len(results), stage="post_grid")
        logger.close()
        print(f"device lost after the grid; progress persisted to "
              f"{resume_path} (rerun with --auto-resume)", file=sys.stderr)
        return 75

    # outputs are published: ANY completed grid consumes a marker so a
    # later --auto-resume cannot replay stale results
    resume.consume()
    logger.log("driver_done", best_reg_weight=results[best_i][0],
               best_metrics=results[best_i][2] or None)
    logger.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
