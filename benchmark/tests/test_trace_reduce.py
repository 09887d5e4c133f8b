"""The trace reduction, on hand-made events and on the small trace
recorded on the v5e (``benchmark/testdata/``: three 3-pass L-BFGS fits at
2^12 rows, 2^14 buckets, from ``--trace 1`` of the harness itself)."""

import gzip
import os
import shutil

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


def test_recorded_trace(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(os.path.join(TESTDATA, "tiny_fit.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    s = tr.reduce_file(str(path))
    # as read off the trace by hand when it was recorded (PERF.md, PR 25)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    assert len(s["piece_gaps_s"]) >= 1
    assert all(g >= 0 for g in s["piece_gaps_s"])
    assert s["top_ops"] and all(sec > 0 for _, sec in s["top_ops"])
    assert not any(name.startswith("while") for name, _ in s["top_ops"])
    assert s["collective_exposed_s"] is None
