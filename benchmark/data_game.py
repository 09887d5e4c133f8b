"""The traffic generator of the GLMix cells: MovieLens-20M-shaped rows.

A row is one rating: a user, an item (movie), a label (rating >= 4) and
three feature shards, as Zhang et al. (GLMix, KDD 2016, section 2) lay
them out:

* ``global`` (the fixed effect): ``fixed_fields`` implicit-one fields a
  row, hashed into ``2**fixed_buckets_log2`` columns — three user-side
  (activity decile, mean-rating bucket, top genre), four item-side
  (release decade, popularity decile, first and second genre) and five
  user x item crosses;
* ``item_feats`` (what the per-user effect multiplies): an intercept and
  the movie's genre indicators, ``1 + genres`` columns, up to 11 of them
  set in a row;
* ``user_feats`` (what the per-item effect multiplies): an intercept, the
  user's activity decile, top two genres and mean-rating bucket as
  indicators, ``1 + 10 + genres + 5`` columns, 5 set in a row.

Rows per user and per item follow the published skew of ml-20m, cut by one
factor (``draw_counts``): every user has at least ``min_rows_per_user``,
the tail reaches ``max_rows_per_user``; items follow a Zipf-Mandelbrot
law whose head holds ``top_item_share`` of all rows and whose tail ends at
one row. Both marginals are exact: the pairing of users with items is a
permutation.

As in ``benchmark/data.py`` the *problem* (who rated what, every
attribute, the planted coefficients, the labels) is drawn from the
configuration's ``data_seed``, and ``--seed`` draws how it is laid out:
a permutation of the rows, a bijection of the hashed columns of
``global`` and a relabelling of the users and of the items. The fit is
invariant under all three up to rounding, so every seed is the same
work, while every gather, the sort behind the CSC view, the bucket an
entity's rows land in and their order all change.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import numpy as np

_HASH_MULT = 2654435761
_FIELD_MULT = 40503
_DECILES = 10
_RATING_BUCKETS = 5
_DECADES = 10


def draw_counts(cfg: dict, rng) -> tuple:
    """-> (rows per user, rows per item), each summing to the rows."""
    n = 1 << int(cfg["rows_log2"])
    users, items = int(cfg["users"]), int(cfg["items"])
    lo, hi = int(cfg["min_rows_per_user"]), int(cfg["max_rows_per_user"])
    hi = min(hi, n // 8)  # a CPU-sized rehearsal has fewer rows than that
    # users: min + a log-normal tail, scaled to the mean and cut at the
    # published maximum; the remainder goes to the heaviest below the cut
    tail = np.exp(rng.normal(0.0, float(cfg["user_tail_sigma"]), users))
    spare = n - lo * users
    if spare < 0:
        raise ValueError("fewer rows than min_rows_per_user a user")
    if spare > users * (hi - lo):
        raise ValueError("more rows than max_rows_per_user a user")
    t_lo, t_hi = 0.0, 1.0
    while np.minimum(tail * t_hi, hi - lo).sum() < spare:
        t_hi *= 2.0
    for _ in range(100):  # the scale at which the cut tail sums to the rows
        mid = 0.5 * (t_lo + t_hi)
        if np.minimum(tail * mid, hi - lo).sum() < spare:
            t_lo = mid
        else:
            t_hi = mid
    extra = np.minimum(tail * t_lo, hi - lo)
    per_user = lo + np.floor(extra).astype(np.int64)
    order = np.argsort(-extra)
    short = n - int(per_user.sum())
    room = order[per_user[order] < hi]
    per_user[room[:short]] += 1
    # items: Zipf-Mandelbrot A (rank + q)^-s, head = top_item_share of the
    # rows, exponent found so that the counts sum to the rows
    top = float(cfg["top_item_share"]) * n
    q = float(cfg["item_zipf_offset"])
    rank = np.arange(items, dtype=np.float64)

    def total(s):
        return np.maximum(top * ((1.0 + q) / (rank + 1.0 + q)) ** s,
                          1.0).sum()

    if total(0.0) < n:
        raise ValueError("top_item_share is under an item's mean share")
    s_lo, s_hi = 0.0, 64.0
    for _ in range(100):
        mid = 0.5 * (s_lo + s_hi)
        s_lo, s_hi = (mid, s_hi) if total(mid) > n else (s_lo, mid)
    raw = np.maximum(top * ((1.0 + q) / (rank + 1.0 + q)) ** s_hi, 1.0)
    per_item = np.floor(raw).astype(np.int64)
    per_item[: n - int(per_item.sum())] += 1  # the remainder, to the head
    if per_user.sum() != n or per_item.sum() != n:
        raise ValueError("the counts do not sum to the rows")
    return per_user, per_item


def _deciles(counts: np.ndarray) -> np.ndarray:
    ranks = np.argsort(np.argsort(counts, kind="stable"), kind="stable")
    return (ranks * _DECILES // len(counts)).astype(np.int64)


def glmix_rows(cfg: dict, seed: int):
    """-> the rows as the seed lays them out: ``global_indices``
    [n, fields] int32, ``item_feats`` / ``user_feats`` (indices int32,
    values float32, dim), ``user`` / ``item`` ids, ``labels`` float64, and
    the rows each user and item has. Every array is made once, in its
    final order: the problem's per-row draws (who, what, the label's
    uniform) are permuted, and the features follow from them."""
    rng = np.random.default_rng(int(cfg["data_seed"]))
    n = 1 << int(cfg["rows_log2"])
    users, items = int(cfg["users"]), int(cfg["items"])
    genres = int(cfg["genres"])
    dim = 1 << int(cfg["fixed_buckets_log2"])
    n_fields = int(cfg["fixed_fields"])
    per_user, per_item = draw_counts(cfg, rng)
    a, b, perm, relabel_u, relabel_i = draw_layout(n, dim, users, items,
                                                   seed)
    u = np.repeat(np.arange(users, dtype=np.int32), per_user)[perm]
    i = rng.permutation(
        np.repeat(np.arange(items, dtype=np.int32), per_item))[perm]

    # attributes
    genre_p = 1.0 / (np.arange(genres) + 2.0)
    genre_p /= genre_p.sum()
    u_decile = _deciles(per_user)
    u_bucket = rng.integers(0, _RATING_BUCKETS, users)
    u_top = rng.choice(genres, users, p=genre_p)
    u_second = (u_top + 1 + rng.integers(0, genres - 1, users)) % genres
    i_decade = rng.integers(0, _DECADES, items)
    i_decile = _deciles(per_item)
    # a movie has 1 to 10 of the genres (mean ~2), drawn without order
    n_genres = np.minimum(1 + rng.poisson(1.1, items), 10)
    keys = rng.random((items, genres)) ** (1.0 / genre_p)
    by_weight = np.argsort(-keys, axis=1)  # weighted draw without replacement
    member = np.arange(genres)[None, :] < n_genres[:, None]
    i_genres = np.where(member, by_weight, -1)  # [items, genres], -1 pad
    i_first = i_genres[:, 0]
    i_second = np.where(n_genres > 1, i_genres[:, 1], genres - 1)

    # planted model; the label's uniform is a row's own draw
    scale = float(cfg["planted_scale"])
    it_dim = 1 + genres
    us_dim = 1 + _DECILES + genres + _RATING_BUCKETS
    b_true = rng.normal(0.0, scale, dim)
    a_true = rng.normal(0.0, scale, (users, it_dim))
    c_true = rng.normal(0.0, scale, (items, us_dim))
    uniform = rng.random(n)[perm]
    logits = np.zeros(n)

    # shard global: 12 hashed fields, a column at a time and in 32 bits:
    # dim is a power of two, so the low bits survive the wrap-around (a
    # [n, 12] int64 temporary costs more in page faults than the
    # arithmetic). A field of the user or the item alone is hashed once
    # an entity and gathered.
    def hashed(value, f):
        base = value.astype(np.uint32) * np.uint32(_HASH_MULT)
        base += np.uint32(f * _FIELD_MULT)
        base &= np.uint32(dim - 1)
        return base

    du, di = u_decile[u], i_decile[i]
    first, top = i_first[i], u_top[u]
    fields = [
        hashed(u_decile, 0)[u], hashed(u_bucket, 1)[u], hashed(u_top, 2)[u],
        hashed(i_decade, 3)[i], hashed(i_decile, 4)[i],
        hashed(i_first, 5)[i], hashed(i_second, 6)[i],
        hashed(u.astype(np.uint32) * np.uint32(genres) + first, 7),
        hashed(i.astype(np.uint32) * np.uint32(_DECILES) + du, 8),
        hashed(top.astype(np.uint32) * np.uint32(items) + i, 9),
        hashed(u_bucket[u] * _DECILES + di, 10),
        hashed(top * genres + i_second[i], 11),
    ]
    if len(fields) != n_fields:
        raise ValueError(f"the generator draws {len(fields)} fields")
    cols = np.empty((n, n_fields), np.int32)
    for f, base in enumerate(fields):
        logits += b_true[base]
        base *= np.uint32(a)  # the seed's bijection of the columns
        base += np.uint32(b)
        base &= np.uint32(dim - 1)
        cols[:, f] = base

    # shard item_feats: intercept 0, genre g at 1 + g
    g_rows = (1 + i_genres[:, :10]).astype(np.int8)[i]  # [n, 10], 0 pad
    it_idx = np.zeros((n, 11), np.int32)
    it_val = np.zeros((n, 11), np.float32)
    it_val[:, 0] = 1.0
    it_idx[:, 1:] = g_rows
    it_val[:, 1:] = g_rows > 0
    for j in range(11):
        logits += a_true[u, it_idx[:, j]] * it_val[:, j]

    # shard user_feats: intercept, decile, two genres, rating bucket
    us_idx = np.empty((n, 5), np.int32)
    us_idx[:, 0] = 0
    us_idx[:, 1] = 1 + du
    us_idx[:, 2] = 1 + _DECILES + top
    us_idx[:, 3] = 1 + _DECILES + u_second[u]
    us_idx[:, 4] = 1 + _DECILES + genres + u_bucket[u]
    for j in range(5):
        logits += c_true[i, us_idx[:, j]]

    labels = (uniform < 1.0 / (1.0 + np.exp(-logits))).astype(np.float64)
    return SimpleNamespace(
        n=n, dim=dim, global_indices=cols,
        item_feats=(it_idx, it_val, it_dim),
        user_feats=(us_idx, np.ones((n, 5), np.float32), us_dim),
        user=relabel_u[u], item=relabel_i[i], labels=labels,
        per_user=per_user, per_item=per_item)


def draw_layout(n: int, dim: int, users: int, items: int, seed: int):
    """-> (a, b, row permutation, user relabelling, item relabelling): the
    part of the inputs ``--seed`` draws."""
    rng = np.random.default_rng(seed)
    while True:
        a = int(rng.integers(1, dim))
        if math.gcd(a, dim) == 1:
            break
    b = int(rng.integers(0, dim))
    return (a, b, rng.permutation(n), rng.permutation(users),
            rng.permutation(items))
