"""GAME scoring driver: batch inference with a saved model.

Equivalent of the reference's ``cli.game.scoring.GameScoringDriver``
(SURVEY.md §4.4; reference mount empty): load a saved GAME model + Avro
data, score every row (fixed-effect margins + per-entity random-effect
margins + offsets), write ``ScoringResultAvro`` records and optionally
evaluate against labels.
"""

from __future__ import annotations

import argparse
import os
from typing import Sequence

import numpy as np

from photon_ml_tpu.game.scoring import score_game_model
from photon_ml_tpu.io.avro import write_avro_file
from photon_ml_tpu.io.data_reader import read_training_examples
from photon_ml_tpu.io.durable import durable_replace
from photon_ml_tpu.io.model_io import load_game_model
from photon_ml_tpu.io.schemas import SCORING_RESULT_SCHEMA
from photon_ml_tpu.evaluation import get_evaluator
from photon_ml_tpu.models import RandomEffectModel
from photon_ml_tpu.utils import (PhotonLogger, Timed,
                                 configure_compile_cache, resolve_dtype)


def _positive_int(value: str) -> int:
    n = int(value)
    if n <= 0:
        raise argparse.ArgumentTypeError(
            f"expected a positive integer, got {value!r}")
    return n


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="GAME scoring driver (TPU-native)")
    p.add_argument("--data", required=True, nargs="+")
    p.add_argument("--model-dir", required=True)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--evaluators", nargs="*", default=())
    p.add_argument("--group-column", default=None,
                   help="metadataMap column keying grouped (Multi-) "
                        "evaluators, e.g. a query id for per_group_auc")
    p.add_argument("--per-coordinate-scores", action="store_true",
                   help="include a per-coordinate score breakdown")
    p.add_argument("--input-columns", default=None,
                   help="JSON (inline or path) remapping record field names")
    p.add_argument("--batch-rows", type=_positive_int, default=None,
                   help="score in row batches of this size (bounds device "
                        "memory for large scoring sets; must be positive "
                        "— 0/negative used to silently produce no output "
                        "rows mid-write)")
    p.add_argument("--out-of-core", action="store_true",
                   help="larger-than-host-RAM scoring: decode block "
                        "windows of ~--batch-rows rows one at a time "
                        "(io/data_reader.read_training_examples_chunked), "
                        "score each, and append its ScoringResult records "
                        "before the next window decodes — host RAM holds "
                        "one window plus O(16B/row) evaluator state")
    p.add_argument("--dtype", default="float32", choices=["float32", "float64"])
    return p


def _scoring_record(uid, score: float, label: float, parts, i: int) -> dict:
    """One ScoringResultAvro record (shared by the resident and
    out-of-core writers)."""
    return {
        "uid": uid,
        "predictionScore": float(score),
        "label": None if np.isnan(label) else float(label),
        "scoreComponents": {k: float(v[i]) for k, v in parts.items()},
    }


def _slice_host_sparse(sp, row_slice):
    from photon_ml_tpu.game.data import HostSparse

    return HostSparse(sp.indices[row_slice], sp.values[row_slice], sp.dim)


def main(argv: Sequence[str] | None = None) -> int:
    configure_compile_cache()
    try:
        return _main(argv)
    except Exception as e:
        # scoring is stateless and its output write is atomic, so device
        # loss needs no marker: exit 75 (EX_TEMPFAIL) and a supervisor
        # rerun is a clean, idempotent retry (same contract as the
        # training drivers)
        from photon_ml_tpu.utils import is_device_loss

        if is_device_loss(e):
            import sys

            print("device lost; rerun this command (scoring is "
                  "idempotent, no partial output was published)",
                  file=sys.stderr)
            return 75
        raise


def _main(argv: Sequence[str] | None = None) -> int:
    args = build_arg_parser().parse_args(argv)
    dtype = resolve_dtype(args.dtype)
    os.makedirs(args.output_dir, exist_ok=True)
    logger = PhotonLogger(os.path.join(args.output_dir, "photon.log.jsonl"))
    logger.log("driver_start", driver="game_scoring", args=vars(args))

    with Timed(logger, "load_model"):
        model = load_game_model(args.model_dir)
    from photon_ml_tpu.io.paldb import load_index_map

    shards = sorted({c.feature_shard for c in model.coordinates.values()})
    index_maps = {
        s: load_index_map(os.path.join(args.model_dir, f"index-map.{s}.json"))
        for s in shards
    }
    entity_columns = [
        c.entity_column for c in model.coordinates.values()
        if isinstance(c, RandomEffectModel) and c.entity_column
    ]
    if args.group_column and args.group_column not in entity_columns:
        entity_columns = entity_columns + [args.group_column]

    from photon_ml_tpu.cli.game_training_driver import _load_input_columns

    if args.out_of_core:
        return _score_out_of_core(args, model, index_maps, entity_columns,
                                  logger, dtype)

    with Timed(logger, "read_data"):
        feats, labels, offsets, weights, ents, uids = read_training_examples(
            args.data, index_maps, entity_columns=entity_columns,
            columns=_load_input_columns(args.input_columns),
            require_response=False,
        )
    logger.log("data_read", num_rows=len(labels))

    def score_rows(row_slice):
        f = {s: _slice_host_sparse(sp, row_slice) for s, sp in feats.items()}
        e = {c: v[row_slice] for c, v in ents.items()}
        result = score_game_model(
            model, f, e, offsets=offsets[row_slice], dtype=dtype,
            per_coordinate=args.per_coordinate_scores,
        )
        if args.per_coordinate_scores:
            s, parts = result
            return np.asarray(s), {k: np.asarray(v) for k, v in parts.items()}
        return np.asarray(result), {}

    with Timed(logger, "score"):
        n = len(labels)
        if n == 0:
            # empty scoring set: a valid, COMPLETE empty output (the
            # atomic write below still runs), not a device no-op that
            # happens to work — downstream consumers see scores.avro
            # with zero records and evaluation is skipped
            chunks = []
        else:
            step = args.batch_rows or n
            chunks = [score_rows(slice(i, min(i + step, n)))
                      for i in range(0, n, step)]
        scores = np.concatenate([c[0] for c in chunks]) if chunks else np.zeros(0)
        parts = {}
        if chunks and chunks[0][1]:
            parts = {k: np.concatenate([c[1][k] for c in chunks])
                     for k in chunks[0][1]}

    with Timed(logger, "write_scores"):
        if len(scores) != len(uids):
            # belt-and-braces: never start streaming records whose score
            # lookups will IndexError halfway through the Avro write
            raise RuntimeError(
                f"scored {len(scores)} rows but read {len(uids)} — "
                "refusing to write a partial scoring set")

        def records():
            for i, uid in enumerate(uids):
                yield _scoring_record(uid, scores[i], labels[i], parts, i)

        _write_scores_atomic(args.output_dir, records())

    labeled = ~np.isnan(labels)
    metrics = {}
    if args.evaluators and not labeled.any():
        logger.log("evaluation_skipped", reason="no labeled rows")
    else:
        group_ids = (ents[args.group_column][labeled]
                     if args.group_column else None)
        for name in args.evaluators:
            ev = get_evaluator(name)
            metrics[name] = ev.evaluate(scores[labeled], labels[labeled],
                                        weights[labeled], group_ids)
    if metrics:
        logger.log("evaluation", **metrics)
    logger.log("driver_done", num_scored=len(scores))
    logger.close()
    return 0



def _write_scores_atomic(output_dir: str, records) -> None:
    """scores.avro appears only when COMPLETE: the writer streams into a
    sibling tmp file that is renamed into place at the end, so a crash
    mid-scoring (device loss) can never leave a partial output a consumer
    would mistake for the full scoring set."""
    final = os.path.join(output_dir, "scores.avro")
    tmp = f"{final}.tmp-{os.getpid()}"
    try:
        write_avro_file(tmp, records, SCORING_RESULT_SCHEMA)
    except BaseException:
        import contextlib

        with contextlib.suppress(OSError):
            os.remove(tmp)
        raise
    durable_replace(tmp, final)

def _score_out_of_core(args, model, index_maps, entity_columns, logger,
                       dtype) -> int:
    """Stream decode -> score -> write, one block window at a time. The
    Avro writer consumes a generator, so output records append as each
    window finishes; only evaluator inputs (scores/labels/weights/groups,
    16B/row) accumulate in host RAM."""
    from photon_ml_tpu.game.scoring import score_game_model
    from photon_ml_tpu.io.data_reader import read_training_examples_chunked

    from photon_ml_tpu.cli.game_training_driver import _load_input_columns

    cols = _load_input_columns(args.input_columns)
    chunk_rows = args.batch_rows or (1 << 16)
    acc_scores, acc_labels, acc_weights, acc_groups = [], [], [], []
    n_scored = [0]

    def scored_records():
        windows = read_training_examples_chunked(
            args.data, index_maps, entity_columns=entity_columns,
            columns=cols, chunk_rows=chunk_rows, require_response=False)
        for feats, labels, offsets, weights, ents, uids in windows:
            result = score_game_model(
                model, feats, ents, offsets=offsets, dtype=dtype,
                per_coordinate=args.per_coordinate_scores)
            if args.per_coordinate_scores:
                scores, parts = result
                parts = {k: np.asarray(v) for k, v in parts.items()}
            else:
                scores, parts = result, {}
            scores = np.asarray(scores)
            if args.evaluators:
                # evaluator state is the ONLY per-row accumulation
                # (16B/row); without evaluators nothing accumulates at all
                acc_scores.append(scores)
                acc_labels.append(labels)
                acc_weights.append(weights)
                if args.group_column:
                    acc_groups.append(ents[args.group_column])
            n_scored[0] += len(scores)
            for i, uid in enumerate(uids):
                yield _scoring_record(uid, scores[i], labels[i], parts, i)

    with Timed(logger, "score_and_write"):
        _write_scores_atomic(args.output_dir, scored_records())

    metrics = {}
    if args.evaluators:
        scores = (np.concatenate(acc_scores) if acc_scores
                  else np.zeros(0))
        labels = (np.concatenate(acc_labels) if acc_labels
                  else np.zeros(0))
        weights = (np.concatenate(acc_weights) if acc_weights
                   else np.zeros(0))
        labeled = ~np.isnan(labels)
        if labeled.any():
            groups = (np.concatenate(acc_groups)[labeled]
                      if acc_groups else None)
            for name in args.evaluators:
                ev = get_evaluator(name)
                metrics[name] = ev.evaluate(scores[labeled],
                                            labels[labeled],
                                            weights[labeled], groups)
        else:
            logger.log("evaluation_skipped", reason="no labeled rows")
    if metrics:
        logger.log("evaluation", **metrics)
    logger.log("driver_done", num_scored=n_scored[0])
    logger.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
