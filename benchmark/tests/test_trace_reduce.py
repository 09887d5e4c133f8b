"""The trace reduction, on hand-made events and on the small trace
recorded on the v5e (``benchmark/testdata/``: three 3-pass L-BFGS fits at
2^12 rows, 2^14 buckets, from ``--trace 1`` of the harness itself)."""

import gzip
import json
import os
import random
import shutil
import time

import pytest

from benchmark import trace_reduce as tr

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
MS = 1e6  # ns


def ops(*spans):
    return [(name, s * MS, e * MS) for name, s, e in spans]


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert tr.total(tr.union([(0, 2), (1, 3), (5, 7)])) == 5
    assert tr.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 4], [6, 8]], []) == [[0, 4], [6, 8]]


def test_busy_idle_gaps_and_pieces_on_hand_made_events():
    # two runs of one program, 10 ms each, 2 ms apart; inside the first a
    # while op spans its two fusions and a 1 ms bubble between them
    events = {0: {
        "ops": ops(("%while.1 = (s32[]) while(...)", 0, 10),
                   ("%fusion.1 = f32[8] fusion(...)", 0, 4),
                   ("%fusion.2 = f32[8] fusion(...)", 5, 10),
                   ("%fusion.1 = f32[8] fusion(...)", 12, 22)),
        "modules": ops(("jit_run(123)", 0, 10), ("jit_run(123)", 12, 22)),
    }, "host": ops(("np.asarray(jax.Array)", 9, 13))}
    s = tr.reduce_events(events)
    assert s["busy_s"] == pytest.approx(0.019)  # the while op is no leaf
    assert s["window_s"] == pytest.approx(0.022)
    assert s["piece_gaps_s"] == pytest.approx([0.002])
    assert s["top_ops"][0] == ["fusion.1", pytest.approx(0.014)]
    assert s["top_gaps"][0][0] == ("between jit_run and jit_run, host in "
                                   "np.asarray(jax.Array)")
    assert s["top_gaps"][0][1] == pytest.approx(0.002)
    assert s["top_gaps"][1][0].startswith("inside jit_run")
    assert s["collective_exposed_s"] is None  # no collective: nothing read


def test_exposed_all_reduce_over_two_chips():
    # chip 0: all-reduce 4..7 alone (the while op around it does not hide
    # it); chip 1: all-reduce 4..7 while a fusion runs 3..6: 1 ms exposed.
    # jax names the op psum: it is known by its opcode
    dev0 = ops(("%while.9 = () while(...)", 0, 10),
               ("%fusion.1 = f32[8] fusion(...)", 0, 4),
               ("%psum.1 = f32[8]{0:T(1024)} all-reduce(f32[8] %x)", 4, 7),
               ("%fusion.2 = f32[8] fusion(...)", 7, 10))
    dev1 = ops(("%fusion.1 = f32[8] fusion(...)", 0, 6),
               ("%all-reduce-start.1 = f32[8] all-reduce-start(...)", 4, 7),
               ("%fusion.2 = f32[8] fusion(...)", 7, 10))
    run = ops(("jit_run(7)", 0, 10))
    s = tr.reduce_events({0: {"ops": dev0, "modules": run},
                          1: {"ops": dev1, "modules": run}, "host": []})
    assert s["devices"] == 2
    assert s["collective_exposed_s"] == pytest.approx((0.003 + 0.001) / 2)
    assert s["collective_window_s"] == pytest.approx(0.010)
    assert s["busy_s"] == pytest.approx(0.010)
    assert s["piece_gaps_s"] == []


def test_a_run_without_its_text_is_busy_but_not_attributed():
    # first run: ops named region.<n>, a loop among them, no module event;
    # second run: named, 1 ms of all-reduce alone in 10 ms
    first = ops(("region.9", 0, 10), ("region.1", 0, 6), ("region.2", 6, 10))
    second = ops(("%fusion.1 = f32[8] fusion(...)", 11, 20),
                 ("%psum.1 = f32[8] all-reduce(f32[8] %x)", 20, 21))
    s = tr.reduce_events({0: {"ops": first + second,
                              "modules": ops(("jit_run(7)", 11, 21))},
                          "host": []})
    assert s["busy_s"] == pytest.approx(0.020)
    assert s["window_s"] == pytest.approx(0.021)
    assert [name for name, _ in s["top_ops"]] == ["fusion.1", "psum.1"]
    assert s["collective_exposed_s"] == pytest.approx(0.001)
    assert s["collective_window_s"] == pytest.approx(0.010)


def test_no_device_op_reads_nothing():
    assert tr.reduce_events({"host": []}) is None
    assert tr.reduce_events({0: {"ops": [], "modules": []},
                             "host": []}) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(os.path.join(TESTDATA, "tiny_fit.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(path)


def test_recorded_trace(recorded):
    s = tr.reduce_events(tr.load_events(recorded))
    # as read off the trace by hand when it was recorded (PERF.md, PR 25)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    assert len(s["piece_gaps_s"]) >= 1
    assert all(g >= 0 for g in s["piece_gaps_s"])
    assert s["top_ops"] and all(sec > 0 for _, sec in s["top_ops"])
    assert not any(name.startswith("while") for name, _ in s["top_ops"])
    assert s["collective_exposed_s"] is None


# -- rank, then label (PR 32): equal to the reduction that labelled every gap --


def label_every_gap(events):
    """The oracle: the two loops as they stood up to PR 31, a label for
    every gap and ``merged`` walked from its start for every piece. -> the
    two keys of the summary those loops fill."""
    dev = min(d for d, v in events.items() if d != "host" and v["ops"])
    v, host = events[dev], events.get("host", [])
    gaps, piece_gaps = [], []
    for d, w in sorted((d, w) for d, w in events.items() if d != "host"):
        merged = tr.union((s, e) for _, s, e in tr.leaf_ops(
            [o for o in w["ops"] if o[2] > o[1]]))
        by_module = {}
        for n, s, e in w["modules"]:
            by_module[n] = by_module.get(n, 0.0) + e - s
        if merged and by_module:
            main = max(by_module, key=by_module.get)
            pieces = sorted([s, e] for n, s, e in w["modules"] if n == main)
            piece_gaps += [tr.total(tr.subtract([[e0, s1]], merged)) / 1e9
                           for (_, e0), (s1, _) in zip(pieces, pieces[1:])]
        if d != dev:
            continue
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            (i0, before), (i1, after) = (tr._module_at(v["modules"], e0 - 1),
                                         tr._module_at(v["modules"], s1 + 1))
            where = (f"inside {before}" if i0 == i1
                     else f"between {before} and {after}")
            label = tr._host_label(host, e0, s1)
            gaps.append([where + (f", host in {label}" if label else ""),
                         (s1 - e0) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return {"top_gaps": gaps[:10], "piece_gaps_s": piece_gaps}


def synthetic(seed, gap_choices, chips=2, runs=12, ops_a_run=30):
    """Seeded events with what a window has: a ``while`` around each run's
    ops, ops that touch (no gap), gaps of few distinct lengths (ties), an
    all-reduce that starts under the op before it, runs that came without
    their text (no program event) and stretches no host span covers. Whole
    nanoseconds, so equal gaps are equal floats."""
    rng = random.Random(seed)
    events, end = {}, 0.0
    for dev in range(chips):
        t, dev_ops, modules = 1000.0 * dev, [], []
        for run in range(runs):
            named = run % 5 != 2
            start = t
            for k in range(ops_a_run):
                dur = float(rng.choice([400, 1000, 2600]))
                if k % 7 == 3 and named:
                    name = "%psum.3 = f32[8]{0} all-reduce(f32[8] %x)"
                    t -= rng.choice([0.0, 200.0])  # partly under the last op
                elif named:
                    name = f"%fusion.{k % 5} = f32[8] fusion(f32[8] %x)"
                else:
                    name = f"region.{k % 5}"
                dev_ops.append((name, t, t + dur))
                t += dur
                if k < ops_a_run - 1:
                    t += float(rng.choice(gap_choices))
            dev_ops.append(("%while.7 = (s32[]) while(...)" if named
                            else "region.9", start, t))
            if named:
                modules.append((f"jit_prog_{run % 2}({run % 2})", start, t))
            t += float(rng.choice(gap_choices[-2:])) + 1000.0
        rng.shuffle(dev_ops)
        events[dev] = {"ops": dev_ops, "modules": modules}
        end = max(end, t)
    host, t = [], 0.0
    while t < end:  # a span, then as long a stretch with none
        dur = float(rng.choice([3000, 20000, 90000]))
        host.append((f"PjitFunction(f{len(host) % 4})", t, t + dur))
        host.append(("np.asarray(jax.Array)", t + dur / 4, t + dur / 2))
        t += dur * rng.choice([1.0, 2.0])
    events["host"] = host
    return events


SYNTHETIC = {
    "mixed-0": (0, [0, 0, 100, 250, 700, 1500]),
    "mixed-1": (1, [0, 0, 100, 250, 700, 1500]),
    "mixed-2": (2, [0, 50, 50, 300, 4000, 9000]),
    "all-ties": (3, [0, 500, 500, 500]),
    "no-touching": (4, [100, 200, 300, 400, 500]),
}


@pytest.mark.parametrize("case", sorted(SYNTHETIC))
def test_ranked_then_labelled_equals_every_gap_labelled(case):
    seed, gap_choices = SYNTHETIC[case]
    events = synthetic(seed, gap_choices)
    s = tr.reduce_events(events)
    assert {k: s[k] for k in ("top_gaps", "piece_gaps_s")} == \
        label_every_gap(events)
    assert len(s["top_gaps"]) == 10 and s["devices"] == 2
    assert s["collective_exposed_s"] > 0


def test_the_synthetic_events_hold_what_they_are_for():
    events = synthetic(*SYNTHETIC["all-ties"])
    gaps = tr.reduce_events(events)["top_gaps"]
    assert len({sec for _, sec in gaps}) == 1  # ten ties, in time order
    every = label_every_gap(synthetic(*SYNTHETIC["mixed-2"]))["top_gaps"]
    assert any("no program" in label for label, _ in every)
    assert any("host in" in label for label, _ in every)
    assert any("host in" not in label for label, _ in every)
    assert any(label.startswith("inside") for label, _ in every)


def test_recorded_trace_equals_every_gap_labelled(recorded):
    events = tr.load_events(recorded)
    s = tr.reduce_events(events)
    assert {k: s[k] for k in ("top_gaps", "piece_gaps_s")} == \
        label_every_gap(events)
    # the whole summary as the reduction of PR 30 read it off this file
    with open(os.path.join(TESTDATA, "tiny_fit.summary.json")) as f:
        assert s == json.load(f)
    assert tr.sizes(events) == {"trace_device_ops": 9540,
                                "trace_host_spans": 195, "trace_programs": 30}


def test_subtract_from_a_known_start():
    b = [[0, 1], [2, 3], [5, 20], [30, 31]]
    for j in range(3):  # the first two end at or before 3
        assert tr.subtract([[3, 40]], b, j) == tr.subtract([[3, 40]], b) \
            == [[3, 5], [20, 30], [31, 40]]


def window_like(n_ops, n_host, n_programs, seconds=20.0):
    """Events of a closed-loop window's sizes: ops back to back with a
    short gap after each, program runs and host spans end to end."""
    rng = random.Random(1)
    span, dt = seconds * 1e9, seconds * 1e9 / n_ops
    return {0: {"ops": [(f"%fusion.{i % 50} = f32[8] fusion(f32[8] %x)",
                         i * dt, i * dt + dt * rng.uniform(0.2, 0.999))
                        for i in range(n_ops)],
                "modules": [(f"jit_prog_{i % 16}(123)", i * span / n_programs,
                             (i + 0.9) * span / n_programs)
                            for i in range(n_programs)]},
            "host": [(f"PjitFunction(x{i})", i * span / n_host,
                      (i + 0.7) * span / n_host) for i in range(n_host)]}


def test_a_glmix_window_reduces_in_a_minute():
    # PR 31's traced window of glmix-ml20m.cd-sweep held 490,840 ops,
    # 12,256 host spans and 2,304 program runs, and labelling every one of
    # its gaps (ops x (host spans + 2 x programs), 2.6 ms a gap) ran past
    # the 1,200 s a run is allowed; a faster sweep has more of all three
    events = window_like(500_000, 12_000, 2_500)
    t = time.perf_counter()
    s = tr.reduce_events(events)
    assert time.perf_counter() - t < 60
    assert len(s["top_gaps"]) == 10
    assert len(s["piece_gaps_s"]) == 2_500 // 16 - 1 + (2_500 % 16 > 0)
