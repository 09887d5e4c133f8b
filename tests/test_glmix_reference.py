"""The program's GLMix against the plain reference, sweep by sweep.

``CoordinateDescent`` (fixed effect by L-BFGS, per-user and per-item
random effects by batched Newton over device-resident tables) against
``benchmark/reference_game.py`` (numpy float64, nothing of the program's),
on seeded random data with Zipf-like row counts: 2^11 rows, 64 users, 24
items. After each of two sweeps: the total objective, each block's
coefficients and the score vector.
"""

import os
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import data_game, reference, reference_game  # noqa: E402
from benchmark.runners import game_cd  # noqa: E402
from photon_ml_tpu.game.data import HostSparse  # noqa: E402
from photon_ml_tpu.game.descent import (  # noqa: E402
    CoordinateConfig,
    CoordinateDescent,
    make_game_dataset,
)

CFG = dict(rows_log2=11, users=64, items=24, genres=20, fixed_fields=12,
           fixed_buckets_log2=12, min_rows_per_user=4, max_rows_per_user=256,
           user_tail_sigma=1.25, top_item_share=0.15, item_zipf_offset=4.0,
           planted_scale=0.3, data_seed=7)
CAPS = {"fixed": 2, "user": 4, "item": 4}
L2 = {"fixed": 1.0, "user": 0.7, "item": 1.3}
NAMES = {"fixed": "fixed", "user": "per-user", "item": "per-item"}
SWEEPS = 2


@pytest.fixture(scope="module")
def rows():
    return data_game.glmix_rows(CFG, seed=2147483659)


@pytest.fixture(scope="module")
def followed(rows):
    with reference.Workers(2) as workers:
        ref = reference_game.Glmix(rows, L2, workers, CFG["users"],
                                   CFG["items"])
        yield ref, ref.follow(SWEEPS, CAPS)


def program_sweeps(rows, dtype):
    """-> the program's model after each sweep (host arrays, the
    reference's layout) and its history."""
    it_idx, it_val, it_dim = rows.item_feats
    us_idx, us_val, us_dim = rows.user_feats
    train = make_game_dataset(
        {"global": HostSparse(rows.global_indices, None, rows.dim),
         "item_feats": HostSparse(it_idx, it_val, it_dim),
         "user_feats": HostSparse(us_idx, us_val, us_dim)},
        rows.labels, entity_ids={"user": rows.user, "item": rows.item})
    random = dict(coordinate_type="random", optimizer="newton",
                  max_iters=CAPS["user"], tolerance=0.0, reg_type="l2",
                  active_set=False)
    configs = [
        CoordinateConfig(name=NAMES["fixed"], feature_shard="global",
                         optimizer="lbfgs", max_iters=CAPS["fixed"],
                         tolerance=0.0, reg_type="l2",
                         reg_weight=L2["fixed"]),
        CoordinateConfig(name=NAMES["user"], feature_shard="item_feats",
                         entity_column="user", reg_weight=L2["user"],
                         **random),
        CoordinateConfig(name=NAMES["item"], feature_shard="user_feats",
                         entity_column="item", reg_weight=L2["item"],
                         **random)]
    models = []

    def keep(it, model):
        models.append(game_cd.model_arrays(
            model, NAMES, CFG["users"], CFG["items"],
            {"user": it_dim, "item": us_dim}))

    _, history = CoordinateDescent(
        configs, n_iterations=SWEEPS, dtype=dtype, dataset_cache={}).run(
            train, checkpoint_callback=keep)
    return models, history


def gaps(ref, records, models, history):
    """Per sweep: the program against the reference's own trajectory."""
    out = []
    start = ref.start()
    losses = [r["train_loss"] for r in history if "train_loss" in r]
    for s, rec in enumerate(records):
        got, want = ref.evaluate(models[s]), ref.evaluate(rec["model"])
        g = {"objective": game_cd.relative_gap(got["objective"],
                                               want["objective"]),
             "scores": game_cd.norm_gap(got["scores"], want["scores"]),
             "train_loss": game_cd.relative_gap(losses[s],
                                                rec["data_loss"])}
        for key in ("fixed", "user", "item"):
            g[key] = game_cd.norm_gap(models[s][key], rec["model"][key],
                                      start[key])
        out.append(g)
    return out


def test_float64_follows_the_reference(rows, followed):
    """Same algorithm, same arithmetic: what is left is the order of the
    sums, a few ulps of float64 grown through two L-BFGS iterations and
    four Newton steps a sweep (Hessians of condition ~1e3). The largest
    number reads 1.2e-9 (per-user change, sweep 2); 1e-8 leaves a wrong
    step, which reads 1e-2 or more, far outside."""
    ref, (_, records) = followed
    models, history = program_sweeps(rows, jnp.float64)
    assert len(models) == SWEEPS
    for s, g in enumerate(gaps(ref, records, models, history)):
        for name, gap in g.items():
            assert gap < 1e-8, (s, name, gap)
    # the reference did descend: the test is not comparing two idle fits
    assert records[1]["objective"] < records[0]["objective"]
    assert records[0]["objective"] < ref.evaluate(ref.start())["objective"]


# float32 against the float64 reference. The float64 run above agrees to
# 1e-9, so what float32 reads is rounding alone: eps(float32) = 6e-8 grown
# through sums over 2^11 rows and the Newton solves' condition numbers.
# Each limit is ten times the larger of the two sweeps' readings on this
# data (in brackets), which leaves a lower precision (bfloat16, eps 4e-3)
# or a wrong step two decades outside.
F32_LIMITS = {
    # sums of 2^11 float32 losses of order 1 [6.4e-7, 9.1e-7]
    "objective": 1e-5, "train_loss": 1e-5,
    # two L-BFGS iterations: the step length moves with the rounding of
    # the line search's trial losses [1.1e-7, 7.9e-5]
    "fixed": 1e-3,
    # four Newton steps an entity, H of condition ~1e3 [2.9e-5, 1.0e-4]
    "user": 1e-3,
    # the same over more rows an entity [1.8e-5, 3.0e-5]
    "item": 3e-4,
    # the three blocks' scores together [1.6e-5, 1.8e-5]
    "scores": 2e-4,
}


def test_float32_stays_within_rounding(rows, followed):
    ref, (_, records) = followed
    models, history = program_sweeps(rows, jnp.float32)
    for s, g in enumerate(gaps(ref, records, models, history)):
        for name, gap in g.items():
            assert gap < F32_LIMITS[name], (s, name, gap)
