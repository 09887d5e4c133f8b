"""GAME training driver: the end-to-end training entry point.

Equivalent of the reference's ``cli.game.training.GameTrainingDriver``
(SURVEY.md §4.1; reference mount empty): parse params, build/load feature
index maps, read Avro training data, optionally normalize, train a GAME
model per optimization-config grid point with validation tracking, select
the best by the primary evaluator, save best + all models (Avro), and
write a structured log. Warm start, locked coordinates (partial retrain),
and per-iteration checkpoints are supported.

Usage:
    python -m photon_ml_tpu.cli.game_training_driver \
        --train-data data/train.avro --validation-data data/val.avro \
        --output-dir out/ --task logistic_regression \
        --coordinates configs/coordinates.json --evaluators auc \
        --n-iterations 3

The coordinate config JSON is a list of dicts matching CoordinateConfig
fields; ``reg_weight`` may be a list to define a grid (cross-product over
coordinates is expanded).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.estimators import GameEstimator
from photon_ml_tpu.evaluation.evaluators import TASK_DEFAULT_EVALUATOR
from photon_ml_tpu.game.descent import CoordinateConfig, GameDataset
from photon_ml_tpu.io.avro import iter_avro_records
from photon_ml_tpu.io.data_reader import read_training_examples
from photon_ml_tpu.io.index_map import IndexMap, build_index_map, filter_index_map
from photon_ml_tpu.io.model_io import load_game_model, save_game_model
from photon_ml_tpu.io.schemas import FEATURE_SUMMARIZATION_SCHEMA
from photon_ml_tpu.ops.losses import TASK_TO_LOSS
from photon_ml_tpu.ops.normalization import NormalizationType, build_normalization_context
from photon_ml_tpu.ops.statistics import summarize_features
from photon_ml_tpu.types import make_batch
from photon_ml_tpu.utils import (PhotonLogger, Timed,
                                 configure_compile_cache, resolve_dtype)


def _positive_int(value: str) -> int:
    n = int(value)
    if n <= 0:
        raise argparse.ArgumentTypeError(
            f"expected a positive integer, got {value!r}")
    return n


def _finite_nonneg_float(value: str) -> float:
    x = float(value)
    if not np.isfinite(x) or x < 0:
        raise argparse.ArgumentTypeError(
            f"expected a finite float >= 0, got {value!r}")
    return x


def _tol_schedule(value: str):
    from photon_ml_tpu.optimize import parse_tolerance_schedule

    try:
        return parse_tolerance_schedule(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="GAME training driver (TPU-native)")
    p.add_argument("--train-data", required=True, nargs="+",
                   help="Avro file(s)/dir(s) of TrainingExampleAvro records")
    p.add_argument("--validation-data", nargs="+", default=None)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task", default="logistic_regression",
                   choices=sorted(TASK_TO_LOSS) + sorted(set(TASK_TO_LOSS.values())))
    p.add_argument("--coordinates", required=True,
                   help="path to coordinate-config JSON, or inline JSON")
    p.add_argument("--evaluators", nargs="*", default=None)
    p.add_argument("--n-iterations", type=int, default=1)
    p.add_argument("--cd-tolerance", type=_finite_nonneg_float, default=0.0,
                   help="sweep-level early exit: stop once every "
                        "coordinate's score vector moved by at most this "
                        "(max-abs) over a whole sweep; 0 disables (exactly "
                        "--n-iterations sweeps run). Must be finite — "
                        "nan/inf would silently disable or always trigger "
                        "the test")
    p.add_argument("--re-active-set", action="store_true", default=None,
                   help="active-set coordinate descent for random effects "
                        "(the CoordinateConfig default): converged "
                        "entities whose coefficients stopped moving are "
                        "frozen and later sweeps solve only the "
                        "unconverged frontier")
    p.add_argument("--no-re-active-set", dest="re_active_set",
                   action="store_false",
                   help="re-solve every entity every sweep (the exact "
                        "fixed-sweep schedule)")
    p.add_argument("--re-refresh-every", type=_positive_int, default=None,
                   help="with the active set: every K-th sweep is a full "
                        "refresh that re-solves frozen entities too, "
                        "re-activating any that drifted because other "
                        "coordinates moved (must be positive)")
    p.add_argument("--solver-tol-schedule", type=_tol_schedule, default=None,
                   metavar="START:DECAY",
                   help="inexact-CD inner-solve tolerance schedule: sweep "
                        "k solves to max(coordinate tolerance, START * "
                        "DECAY^k) — loose early sweeps, geometrically "
                        "tightening to the configured tolerance (e.g. "
                        "1e-3:0.1; 'off' disables)")
    p.add_argument("--index-map", default=None,
                   help="prebuilt index map (JSON, native store, or hashing "
                        "config; else built from data)")
    p.add_argument("--hash-dim", type=int, default=None,
                   help="feature-hash into this width instead of building an "
                        "index map (TB-scale path; collisions accepted)")
    p.add_argument("--feature-shards", default=None,
                   help="JSON (inline or path): shard name -> list of feature-"
                        "name prefixes (per-shard feature bags); shards not "
                        "listed get all features")
    p.add_argument("--min-feature-count", type=int, default=1)
    p.add_argument("--input-columns", default=None,
                   help="JSON (inline or path) remapping record field names "
                        "(response/offset/weight/uid/features/metadata_map)")
    p.add_argument("--add-intercept", action="store_true", default=True)
    p.add_argument("--no-intercept", dest="add_intercept", action="store_false")
    p.add_argument("--normalization", default="none",
                   choices=[t.value for t in NormalizationType])
    p.add_argument("--warm-start-model", default=None,
                   help="model dir to warm start from")
    p.add_argument("--locked-coordinates", nargs="*", default=(),
                   help="coordinates kept fixed (partial retrain)")
    p.add_argument("--checkpoint", action="store_true",
                   help="save the model after each outer CD iteration")
    p.add_argument("--auto-resume", action="store_true",
                   help="with --checkpoint: adopt the latest checkpoint as "
                        "the warm start when a prior run died on device "
                        "loss (see the RESUME marker / exit code 75)")
    p.add_argument("--save-all-models", action="store_true")
    p.add_argument("--publish-to", default=None,
                   help="model-registry root (registry/): publish the "
                        "best model there as an immutable version after "
                        "saving. The FIRST publish into an empty "
                        "registry also sets LATEST (bootstrap); later "
                        "versions are promoted through the gate "
                        "(photon-model-publish --gate-data ...)")
    p.add_argument("--summarize-features", action="store_true",
                   help="write FeatureSummarizationResultAvro output")
    p.add_argument("--dtype", default="float32", choices=["float32", "float64"])
    p.add_argument("--streaming", action="store_true",
                   help="larger-than-HBM mode for fixed-effect coordinates: "
                        "features stay in host RAM, each optimizer pass "
                        "streams fixed-shape chunks through the device")
    p.add_argument("--pad-nnz", type=int, default=None,
                   help="fixed per-row feature width incl. intercept for "
                        "--out-of-core-shards sources (default: one "
                        "measuring decode pass per shard — pass the known "
                        "value at scale to skip it)")
    p.add_argument("--out-of-core-shards", nargs="*", default=(),
                   help="feature shards that must NEVER materialize in "
                        "host RAM: their coordinates (streaming fixed "
                        "effects) re-decode Avro block waves from disk "
                        "every optimizer pass (io/stream_source.py); "
                        "multi-process runs give each process its own "
                        "contiguous block share. Requires a pinned "
                        "feature space (--hash-dim or --index-map); "
                        "normalization works via a streamed "
                        "summarization pass")
    p.add_argument("--chunk-rows", type=int, default=1 << 16,
                   help="rows per streamed chunk (--streaming)")
    p.add_argument("--chunk-cache-dir", default=None,
                   help="with --out-of-core-shards: decode-once packed "
                        "chunk cache root (io/chunk_cache.py; one subdir "
                        "per shard) — the first streamed pass spills "
                        "decoded chunks into packed memmaps, every later "
                        "pass (and every CD iteration) streams them back "
                        "decode-free; CD residual offsets still update "
                        "through the scalar overlay. Invalidated when "
                        "source files / chunk geometry / index map "
                        "change; multi-process runs need per-process dirs")
    p.add_argument("--chunk-cache-gb", type=float, default=None,
                   help="per-shard disk budget for --chunk-cache-dir; a "
                        "shard that doesn't fit falls through to "
                        "re-decode with a logged warning")
    p.add_argument("--prefetch-depth", type=int, default=None,
                   help="streamed transfer-ring depth: chunks staged on "
                        "device ahead of compute (default 2 / "
                        "PHOTON_PREFETCH_DEPTH; 0 = synchronous)")
    p.add_argument("--tuning-mode", default="none",
                   choices=["none", "random", "bayesian"],
                   help="auto-tune reg weights after the grid (SURVEY.md §4.5)")
    p.add_argument("--tuning-iters", type=int, default=10)
    p.add_argument("--tuning-range", type=float, nargs=2, default=(1e-4, 1e4),
                   metavar=("LOW", "HIGH"),
                   help="log-scale search range for regularization weights")
    p.add_argument("--tuning-coordinates", nargs="*", default=None,
                   help="coordinates whose reg weights are tuned (default: all "
                        "unlocked)")
    p.add_argument("--tuning-seed", type=int, default=0)
    p.add_argument("--coordinator-address", default=None,
                   help="multi-host: coordinator host:port for "
                        "jax.distributed.initialize (every process runs this "
                        "driver with the same args)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--entity-shards", type=_positive_int, default=None,
                   help="entity-sharded random-effect training: partition "
                        "every random coordinate's entity table across this "
                        "many processes by a stable hash of the entity id "
                        "(must equal the controller process count — shard i "
                        "lives on process i). Each process builds and "
                        "solves only its owned entities; sweeps exchange "
                        "only changed rows' scores, never coefficients "
                        "(parallel/entity_shard.py, docs/sharding.md)")
    p.add_argument("--re-table-budget-mb", type=float, default=None,
                   help="per-process random-effect entity-table budget in "
                        "MB: a coordinate whose LOCAL table exceeds it "
                        "fails fast with a pointer at --entity-shards "
                        "instead of silently exhausting host RAM")
    p.add_argument("--max-rank-failures", type=int, default=0,
                   help="in-job elastic recovery: tolerate up to this many "
                        "cumulative rank losses by shrinking onto the "
                        "surviving process set and redistributing the dead "
                        "ranks' entities from the last committed per-sweep "
                        "snapshot (transports that cannot resize — the "
                        "production jax runtime — still get transient "
                        "rollback-retry and escalate rank loss to the "
                        "--auto-resume whole-job path). 0 (default) keeps "
                        "the plain fail-stop behavior "
                        "(parallel/recovery.py, docs/resilience.md)")
    p.add_argument("--recovery-snapshot-every", type=_positive_int,
                   default=1,
                   help="commit a recovery snapshot every N CD sweeps "
                        "(with --max-rank-failures > 0): a failure rolls "
                        "back at most N sweeps; larger N trades snapshot "
                        "time for replay time")
    p.add_argument("--profile-dir", default=None,
                   help="capture a JAX profiler trace of training here "
                        "(view in TensorBoard/Perfetto)")
    p.add_argument("--trace-dir", default=None,
                   help="write photon-trace span files here (one "
                        "trace-rankN.json per process, Chrome-trace "
                        "format; merge with `photon-trace merge`). "
                        "Also honors PHOTON_TRACE / PHOTON_TRACE_SAMPLE "
                        "(obs/trace.py, docs/observability.md)")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="fraction of traces recorded under --trace-dir")
    return p


def _load_input_columns(spec):
    from photon_ml_tpu.io.data_reader import InputColumnsNames

    if not spec:
        return InputColumnsNames()
    if os.path.exists(spec):
        with open(spec) as f:
            return InputColumnsNames.from_dict(json.load(f))
    return InputColumnsNames.from_dict(json.loads(spec))


def _load_coordinate_grid(spec: str) -> List[List[CoordinateConfig]]:
    if os.path.exists(spec):
        with open(spec) as f:
            raw = json.load(f)
    else:
        raw = json.loads(spec)
    if not isinstance(raw, list) or not raw:
        raise ValueError("coordinate config must be a non-empty JSON list")
    # expand list-valued reg_weight into a grid (the reference's grid of
    # GameOptimizationConfigurations — SURVEY.md §4.1)
    per_coord_options: List[List[dict]] = []
    for c in raw:
        weights = c.get("reg_weight", 0.0)
        if isinstance(weights, list):
            per_coord_options.append([{**c, "reg_weight": w} for w in weights])
        else:
            per_coord_options.append([c])
    grid = []
    for combo in itertools.product(*per_coord_options):
        grid.append([CoordinateConfig(**c) for c in combo])
    return grid


def _entity_columns(grid) -> List[str]:
    cols = []
    for cfg in grid[0]:
        if cfg.coordinate_type == "random" and cfg.entity_column not in cols:
            cols.append(cfg.entity_column)
    return cols


def _read_dataset(paths, index_maps, entity_columns, columns=None) -> GameDataset:
    feats, labels, offsets, weights, ents, uids = read_training_examples(
        paths, index_maps, entity_columns=entity_columns, columns=columns
    )
    return GameDataset(feats, labels, weights, offsets, ents, None)


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
        # every exit path (incl. the device-loss return 75) exports the
        # trace files so a crashed run still leaves its spans behind.
        # Only stop a tracer THIS invocation started: in the simulated
        # harness several ranks run main() in one process and only one
        # of them owns the process-wide tracer.
        if started is not None:
            obs_trace.stop()


def _run(args) -> int:
    from photon_ml_tpu.parallel import resilience
    from photon_ml_tpu.parallel.multihost import initialize_multihost, runtime_info

    distributed = initialize_multihost(args.coordinator_address,
                                       args.num_processes, args.process_id)
    # lead election through the ambient transport, not jax: identical in
    # a real multi-controller run, and under the simulated harness every
    # thread shares jax.process_index()==0 while the transport reports
    # the true per-rank index — without this, all simulated ranks think
    # they lead and race their saves to the shared output dir
    is_lead = ((not distributed) or jax.process_index() == 0) \
        and resilience.current_process_index() == 0
    # entity sharding is argv-validated HERE, before any data read: the
    # owner map assigns shard i to process i, so the shard count must be
    # the controller process count
    entity_spec = None
    if args.entity_shards is not None:
        # the transport's view, not jax's: identical in a real
        # multi-controller run, and the simulated harness's per-thread
        # transports report their group size here
        tp = resilience.current_transport()
        pc = tp.process_count()
        if args.entity_shards != pc:
            raise SystemExit(
                f"--entity-shards {args.entity_shards} must equal the "
                f"controller process count ({pc}): the owner map assigns "
                "entity shard i to process i (run one process per shard "
                "via --coordinator-address/--num-processes)")
        from photon_ml_tpu.parallel.entity_shard import EntityShardSpec

        entity_spec = EntityShardSpec(
            args.entity_shards, resilience.current_process_index())
    re_table_budget = (None if args.re_table_budget_mb is None
                       else int(args.re_table_budget_mb * 1e6))
    dtype = resolve_dtype(args.dtype)
    task = TASK_TO_LOSS.get(args.task, args.task)
    os.makedirs(args.output_dir, exist_ok=True)
    logger = PhotonLogger(os.path.join(args.output_dir, "photon.log.jsonl"))
    logger.log("driver_start", driver="game_training", args=vars(args),
               distributed=distributed, **runtime_info())

    columns = _load_input_columns(args.input_columns)
    grid = _load_coordinate_grid(args.coordinates)
    if args.streaming:
        import dataclasses as _dc

        grid = [
            [_dc.replace(cfg, streaming=True, chunk_rows=args.chunk_rows)
             if cfg.coordinate_type == "fixed" else cfg
             for cfg in configs]
            for configs in grid
        ]
    if args.prefetch_depth is not None:
        import dataclasses as _dc

        grid = [
            [_dc.replace(cfg, prefetch_depth=args.prefetch_depth)
             if cfg.coordinate_type == "fixed" else cfg
             for cfg in configs]
            for configs in grid
        ]
    re_overrides = {
        k: v for k, v in (("active_set", args.re_active_set),
                          ("refresh_every", args.re_refresh_every))
        if v is not None
    }
    if re_overrides:  # apply to every random coordinate across the grid
        import dataclasses as _dc

        grid = [
            [_dc.replace(cfg, **re_overrides)
             if cfg.coordinate_type == "random" else cfg
             for cfg in configs]
            for configs in grid
        ]
    shards = sorted({cfg.feature_shard for cfg in grid[0]})
    entity_columns = _entity_columns(grid)

    # fail fast on bad tuning flags — tuning runs AFTER the (possibly long)
    # grid training, so catching these there would waste the whole run
    tuned_coords = None
    if args.tuning_mode != "none":
        if not args.validation_data:
            raise SystemExit("--tuning-mode requires --validation-data")
        lo, hi = args.tuning_range
        if not (0 < lo < hi):
            raise SystemExit(f"--tuning-range needs 0 < LOW < HIGH, got "
                             f"{lo} {hi}")
        if args.evaluators is not None and not args.evaluators:
            raise SystemExit("--tuning-mode needs at least one evaluator "
                             "(drop the bare --evaluators flag to use the "
                             "task default)")
        from photon_ml_tpu.tuning import resolve_tuned_coordinates

        try:
            tuned_coords = resolve_tuned_coordinates(
                grid[0], args.tuning_coordinates, args.locked_coordinates
            )
        except ValueError as e:
            raise SystemExit(f"--tuning-coordinates: {e}")

    with Timed(logger, "feature_indexing"):
        if args.hash_dim:
            from photon_ml_tpu.io.hashing import HashingIndexMap

            base_map = HashingIndexMap(args.hash_dim,
                                       add_intercept=args.add_intercept)
        elif args.index_map:
            from photon_ml_tpu.io.paldb import load_index_map

            base_map = load_index_map(args.index_map)
        else:
            base_map = build_index_map(
                iter_avro_records(args.train_data),
                add_intercept=args.add_intercept,
                min_count=args.min_feature_count,
                features_field=columns.features,
            )
        shard_defs = {}
        if args.feature_shards:
            if os.path.exists(args.feature_shards):
                shard_defs = json.load(open(args.feature_shards))
            else:
                shard_defs = json.loads(args.feature_shards)
            if args.hash_dim and any(s in shard_defs for s in shards):
                raise SystemExit(
                    "--hash-dim cannot be combined with feature-shard prefix "
                    "filtering (a hashing map has no enumerable features); "
                    "give each shard its own driver run or drop --hash-dim"
                )
        index_maps: Dict[str, IndexMap] = {}
        for s in shards:
            if s in shard_defs:
                index_maps[s] = filter_index_map(
                    base_map, shard_defs[s], add_intercept=args.add_intercept
                )
            else:
                index_maps[s] = base_map

    ooc_shards = set(args.out_of_core_shards or ())
    if args.chunk_cache_dir and not ooc_shards:
        raise SystemExit("--chunk-cache-dir requires --out-of-core-shards "
                         "(only disk-backed shards re-decode per pass)")
    if args.chunk_cache_gb is not None and not args.chunk_cache_dir:
        raise SystemExit("--chunk-cache-gb requires --chunk-cache-dir")
    if ooc_shards:
        # every check here is argv-only: fail BEFORE the (potentially
        # hours-long at the scale this feature targets) dataset reads
        unknown = ooc_shards - set(shards)
        if unknown:
            raise SystemExit(f"--out-of-core-shards: {sorted(unknown)} not "
                             f"used by any coordinate (shards: {sorted(shards)})")
        if not (args.hash_dim or args.index_map):
            raise SystemExit("--out-of-core-shards needs a pinned feature "
                             "space (--hash-dim or --index-map): building "
                             "an index map scans the full dataset")
        # only streaming FIXED coordinates can consume a disk-backed
        # shard; a random coordinate's data layer needs resident features
        ooc_chunk_rows: Dict[str, int] = {}
        for cfg in grid[0]:
            if cfg.feature_shard not in ooc_shards:
                continue
            if cfg.coordinate_type != "fixed" or not cfg.streaming:
                raise SystemExit(
                    f"--out-of-core-shards: shard '{cfg.feature_shard}' is "
                    f"used by coordinate '{cfg.name}' "
                    f"({cfg.coordinate_type}"
                    f"{'' if cfg.streaming else ', streaming=false'}) — "
                    "only streaming fixed-effect coordinates can train "
                    "from a disk-backed shard")
            ooc_chunk_rows[cfg.feature_shard] = min(
                cfg.chunk_rows,
                ooc_chunk_rows.get(cfg.feature_shard, cfg.chunk_rows))

    with Timed(logger, "read_train_data"):
        train = _read_dataset(
            args.train_data,
            {s_: m for s_, m in index_maps.items() if s_ not in ooc_shards},
            entity_columns, columns)
        if ooc_shards:
            from photon_ml_tpu.io.stream_source import AvroChunkSource

            n_local = max(len(jax.local_devices()), 1)

            def _cr(shard):
                # the consuming coordinate's chunk_rows (min across
                # coordinates sharing the shard), device-rounded
                base = ooc_chunk_rows.get(shard, args.chunk_rows)
                return -(-base // n_local) * n_local

            # multi-process: each process keeps its own contiguous block
            # share; per-pass partials reduce across processes and scoring
            # reassembles via the recorded part spans
            part = ((jax.process_index(), jax.process_count())
                    if distributed else None)
            train.feature_sources = {
                s_: AvroChunkSource(args.train_data, index_maps[s_],
                                    chunk_rows=_cr(s_), columns=columns,
                                    pad_nnz=args.pad_nnz, dtype=dtype,
                                    process_part=part)
                for s_ in ooc_shards
            }
            if args.chunk_cache_dir:
                # decode-once: the first streamed pass over each shard
                # (summarization or the first fit pass) pays the Avro
                # decode; every later pass — including every CD
                # iteration's 2 sparse passes — streams packed memmaps
                from photon_ml_tpu.io.chunk_cache import ChunkCacheSource

                cache_bytes = (None if args.chunk_cache_gb is None
                               else int(args.chunk_cache_gb * 1e9))
                train.feature_sources = {
                    s_: ChunkCacheSource(
                        src_, os.path.join(args.chunk_cache_dir, s_),
                        max_bytes=cache_bytes)
                    for s_, src_ in train.feature_sources.items()
                }
    validation = None
    if args.validation_data:
        with Timed(logger, "read_validation_data"):
            validation = _read_dataset(args.validation_data, index_maps,
                                       entity_columns, columns)
    logger.log("data_read", num_train=train.num_samples,
               num_validation=0 if validation is None else validation.num_samples,
               num_features={s: m.size for s, m in index_maps.items()})

    norm_type = NormalizationType(args.normalization)
    if norm_type != NormalizationType.NONE or args.summarize_features:
        contexts = {}
        # feature summarization is the first collective phase of a
        # multi-controller run (the streamed-moment all-reduce): run it
        # under the health guard so one process's read/decode failure
        # aborts every process instead of wedging the reduce
        with Timed(logger, "feature_summarization"), \
                resilience.CollectiveGuard("feature_summarization"):
            for shard in shards:
                if shard in ooc_shards:
                    # one extra streamed pass over the disk-backed shard:
                    # per-feature moments without a resident copy. A
                    # multi-controller run streams only the local block
                    # part, so the raw moments are all-reduced and
                    # finalized against the GLOBAL row count — otherwise
                    # each process would build a normalization context
                    # from its own data half and the summed gradients
                    # would mix feature spaces.
                    from photon_ml_tpu.ops.statistics import (
                        summarize_features_streamed,
                    )
                    from photon_ml_tpu.parallel.multihost import (
                        allreduce_summary_moments,
                    )

                    src = train.feature_sources[shard]
                    summary = summarize_features_streamed(
                        src, src.dim, src.rows,
                        total_rows=src.total_rows,
                        part_reduce=(allreduce_summary_moments
                                     if distributed else None))
                else:
                    sp = train.features[shard]
                    batch = make_batch(_to_sparse_features(sp), train.labels)
                    summary = summarize_features(batch)
                if args.summarize_features and is_lead:
                    _write_summary(args.output_dir, summary, index_maps[shard],
                                   suffix=shard)
                if norm_type != NormalizationType.NONE:
                    contexts[shard] = build_normalization_context(
                        norm_type, summary,
                        intercept_index=index_maps[shard].intercept_index,
                    )
        if norm_type != NormalizationType.NONE:
            grid = [
                [_with_normalization(cfg, contexts[cfg.feature_shard],
                                     index_maps[cfg.feature_shard])
                 for cfg in configs]
                for configs in grid
            ]

    warm = load_game_model(args.warm_start_model) if args.warm_start_model else None
    # Unified resume-marker lifecycle (parallel/resilience.ResumeManager):
    # written atomically on device loss, KEPT until this run completes (a
    # second failure of any kind — OOM, SIGKILL, another device loss —
    # must not discard resume state; same semantics as the GLM driver's
    # RESUME_GLM.npz), and fingerprinted against the inputs so a rerun
    # pointed at different data refuses to resume instead of silently
    # mixing datasets.
    resume = resilience.ResumeManager(
        os.path.join(args.output_dir, "RESUME.json"),
        fingerprint={
            "train_data": sorted(args.train_data),
            "validation_data": (sorted(args.validation_data)
                                if args.validation_data else None),
            "validation_rows": (None if validation is None
                                else int(validation.num_samples)),
        },
        is_lead=is_lead)
    if args.auto_resume and resume.exists():
        # marker-gated ONLY: without it --auto-resume is a no-op, so a
        # supervisor can pass the flag unconditionally without a cleanly
        # finished run's leftover checkpoints hijacking later reruns
        resume_from = resume.load().get("checkpoint")
        if resume_from:
            warm = load_game_model(resume_from)
            logger.log("auto_resume", checkpoint=resume_from)
    if args.auto_resume and distributed:
        # every process must have adopted the checkpoint (or observed
        # its absence) before any enters training's first collective;
        # the health barrier doubles as the ordering sync and surfaces
        # a peer whose marker load failed. It runs UNCONDITIONALLY of
        # resume.exists(): that is a process-LOCAL filesystem probe, and
        # a marker visible on only some hosts (eventual-consistency
        # shared FS mid-write) would otherwise send part of the job to
        # this barrier while the rest proceeds to training — diverging
        # the collective sequences (photon-check PC102).
        resilience.health_barrier("auto_resume_loaded")

    evaluators = args.evaluators
    if evaluators is None:
        evaluators = [TASK_DEFAULT_EVALUATOR[task]] if validation is not None else []

    recovery_mgr = None
    if args.max_rank_failures > 0:
        from photon_ml_tpu.parallel.recovery import RecoveryManager

        # same fingerprint discipline as the resume marker: a recovery
        # snapshot from a run over different inputs must refuse to load
        recovery_mgr = RecoveryManager(
            os.path.join(args.output_dir, "recovery"),
            fingerprint=resume.fingerprint,
            max_rank_failures=args.max_rank_failures,
            snapshot_every=args.recovery_snapshot_every)

    estimator = GameEstimator(
        task=task, n_iterations=args.n_iterations, evaluators=evaluators,
        dtype=dtype, cd_tolerance=args.cd_tolerance,
        solver_tol_schedule=args.solver_tol_schedule,
        entity_shard=entity_spec,
        entity_table_budget_bytes=re_table_budget,
        recovery=recovery_mgr,
    )
    ckpt = None
    if args.checkpoint and is_lead:
        # lead-only: every process reaches the same model and output_dir
        # is shared, so concurrent saves to one checkpoint path would
        # race the atomic rename-into-place
        def ckpt(gi, it, model):
            path = os.path.join(args.output_dir, "checkpoints",
                                f"config-{gi}-iter-{it}")
            save_game_model(model, path, index_maps)
            logger.log("checkpoint", config=gi, iteration=it, path=path)
    elif args.checkpoint and entity_spec is not None and entity_spec.active:
        # entity-sharded checkpoints are a collective (the per-iteration
        # model build gathers every shard's buckets): non-lead processes
        # must still participate in the gather, they just don't write
        def ckpt(gi, it, model):
            del gi, it, model  # gathered; the lead wrote it

    def log_fit(gi, result):
        for rec in result.history:
            logger.log("cd_iteration", config=gi, **rec)

    from photon_ml_tpu.obs.trace import profile

    # Device-loss recovery (SURVEY §5.3): a TPU worker crash surfaces as
    # JaxRuntimeError("UNAVAILABLE ...") and the dead backend cannot be
    # reinitialized IN-PROCESS (builder-measured on a v5e, 2026-07-31,
    # not re-measured since: a worker crash required a fresh process even
    # though the worker itself recovered in ~90 s). So recovery is a
    # process boundary: persist a RESUME marker pointing at the newest
    # checkpoint and exit 75 (EX_TEMPFAIL); a supervisor reruns the same
    # command with --auto-resume, which adopts that checkpoint as the
    # warm start. --auto-resume consumed the marker above.
    try:
        with Timed(logger, "training"), profile(args.profile_dir):
            results = estimator.fit(
                train, validation, config_grid=grid, warm_start=warm,
                locked=args.locked_coordinates, checkpoint_callback=ckpt,
                fit_callback=log_fit,
            )
    except Exception as e:
        from photon_ml_tpu.utils import is_device_loss

        if not is_device_loss(e) or not args.checkpoint:
            raise
        latest = _latest_checkpoint(args.output_dir)
        resume.save({"error": str(e).split("\n")[0], "checkpoint": latest})
        logger.log("device_lost", error=str(e).split("\n")[0],
                   resume_checkpoint=latest)
        logger.close()
        print(f"device lost; resume marker written to {resume.path} "
              "(rerun with --auto-resume)", file=sys.stderr)
        return 75

    if recovery_mgr is not None and recovery_mgr.stats["recoveries"]:
        # the run survived at least one in-job recovery: record it in the
        # run log (the supervisor never saw a restart, so this is the
        # only durable trace of the event)
        logger.log("in_job_recovery", **recovery_mgr.as_dict())

    if args.tuning_mode != "none":
        from photon_ml_tpu.tuning import tune_game

        def log_tune(ri, result):
            logger.log("tuning_round", round=ri,
                       reg_weights={c.name: c.reg_weight for c in result.configs},
                       metrics=result.evaluation.metrics)

        with Timed(logger, "hyperparameter_tuning"):
            tuned = tune_game(
                estimator, train, validation, list(grid[0]),
                n_iterations=args.tuning_iters, mode=args.tuning_mode,
                reg_range=tuple(args.tuning_range), prior_results=results,
                seed=args.tuning_seed, tuned_coordinates=tuned_coords,
                fit_callback=log_tune, warm_start=warm,
                locked=args.locked_coordinates,
            )
        results = results + tuned

    best = estimator.select_best(results)
    with Timed(logger, "save_models"):
        # every process reaches the same model; only the lead writes, so
        # co-located multi-controller processes never interleave writes
        # to one output path
        if is_lead:
            save_game_model(best.model, os.path.join(args.output_dir, "best"),
                            index_maps)
            if args.save_all_models:
                for gi, r in enumerate(results):
                    save_game_model(
                        r.model,
                        os.path.join(args.output_dir, "all", f"config-{gi}"),
                        index_maps)
    if args.publish_to and is_lead:
        from photon_ml_tpu.registry import ModelRegistry

        registry = ModelRegistry(args.publish_to)
        best_metrics = ({} if best.evaluation is None
                        else dict(best.evaluation.metrics))
        bootstrap = registry.read_latest(retries=1) is None
        version = registry.publish(
            os.path.join(args.output_dir, "best"),
            metrics=best_metrics, set_latest=bootstrap)
        logger.log("model_published", registry=args.publish_to,
                   version=version, set_latest=bootstrap,
                   metrics=best_metrics)
    # outputs are published: ANY completed run consumes the marker (not
    # only --auto-resume ones) so a later auto-resume cannot warm-start
    # from a checkpoint that predates these outputs
    resume.consume()
    logger.log("driver_done",
               best_config=[dataclasses_asdict(c) for c in best.configs],
               best_metrics=None if best.evaluation is None else best.evaluation.metrics)
    logger.close()
    return 0


def _latest_checkpoint(output_dir: str):
    """Newest checkpoint dir, or None. mtime first; ties (coarse-mtime
    filesystems) break on the PARSED config/iteration numbers — a
    lexicographic tiebreak would order iter-9 above iter-10."""
    import re

    root = os.path.join(output_dir, "checkpoints")
    if not os.path.isdir(root):
        return None

    def nums(name):
        return tuple(int(x) for x in re.findall(r"\d+", name)) or (-1,)

    entries = [d for d in sorted(os.listdir(root))
               if os.path.isdir(os.path.join(root, d))]
    live = [d for d in entries if ".tmp-" not in d and ".old-" not in d]
    # crash-window recovery: save_game_model's overwrite swap can die
    # between its two renames, leaving only a complete '{name}.old-{pid}'
    # copy; count it as its base name when the base is missing
    for d in entries:
        if ".old-" in d:
            base = d.split(".old-")[0]
            if base not in live:
                live.append(d)
    if not live:
        return None
    best = max(live, key=lambda d: (os.path.getmtime(os.path.join(root, d)),
                                    nums(d)))
    return os.path.join(root, best)


def _to_sparse_features(sp):
    from photon_ml_tpu.types import SparseFeatures

    return SparseFeatures(jnp.asarray(sp.indices), jnp.asarray(sp.values),
                          dim=sp.dim)


def _with_normalization(cfg: CoordinateConfig, ctx, imap: IndexMap):
    import dataclasses as _dc

    return _dc.replace(cfg, normalization=ctx,
                       intercept_index=imap.intercept_index)


def dataclasses_asdict(cfg: CoordinateConfig) -> dict:
    import dataclasses as _dc

    d = _dc.asdict(cfg)
    d.pop("normalization", None)  # device arrays aren't JSON
    return d


def _write_summary(output_dir, summary, imap: IndexMap, suffix: str = "global"):
    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.schemas import split_feature_key

    inverse = imap.inverse()

    def records():
        for i in range(summary.dim):
            name, term = split_feature_key(inverse[i])
            yield {
                "name": name, "term": term,
                "mean": float(summary.mean[i]),
                "variance": float(summary.variance[i]),
                "min": float(summary.min[i]), "max": float(summary.max[i]),
                "numNonzeros": float(summary.num_nonzeros[i]),
                "count": summary.count,
            }

    name = ("feature-summary.avro" if suffix == "global"
            else f"feature-summary.{suffix}.avro")
    write_avro_file(os.path.join(output_dir, name),
                    records(), FEATURE_SUMMARIZATION_SCHEMA)


if __name__ == "__main__":
    raise SystemExit(main())
