"""``photon-trace gaps`` on a hand-made profiler trace: a device's idle
time between leaf ops, each part put down to the innermost photon span on
the host that covers it.

The file is written here in the ``XSpace`` wire format
(``tsl/profiler/protobuf/xplane.proto``): a TPU plane whose ``XLA Ops`` line
holds seven leaf ops (two of them inside a ``while``), and the host's plane
with a thread that ran three nested spans beside two runtime events, and
a thread that ran none. Times below are microseconds.
"""

import gzip
import json

import pytest

from photon_ml_tpu.obs import trace_cli, xplane

US = 10**6  # picoseconds


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _int(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(v)


def _bytes(field: int, payload) -> bytes:
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name: str, lines) -> bytes:
    """``lines``: [(line name, timestamp_ns, [(event name, start_us,
    end_us)])], event times relative to the plane's first line."""
    ids, body = {}, b""
    base_ns = lines[0][1]
    for line_name, ts_ns, events in lines:
        evs = b""
        for ev_name, s0, s1 in events:
            mid = ids.setdefault(ev_name, len(ids) + 1)
            offset = s0 * US - (ts_ns - base_ns) * 1000
            evs += _bytes(4, _int(1, mid) + _int(2, offset)
                          + _int(3, (s1 - s0) * US))
        body += _bytes(3, _bytes(2, line_name) + _int(3, ts_ns) + evs)
    for ev_name, mid in ids.items():
        body += _bytes(4, _int(1, mid) + _bytes(2, _int(1, mid)
                                              + _bytes(2, ev_name)))
    return _bytes(1, _bytes(2, name) + body)


DEVICE = [("a", 0, 5), ("b", 12, 22), ("while.1", 25, 50), ("c", 25, 35),
          ("d", 40, 50), ("e", 70, 80), ("f", 90, 95), ("g", 120, 130)]
HOST = [("cd.sweep", 0, 100), ("cd.coordinate", 10, 60),
        ("cd.fetch#what=train_loss#", 20, 30), ("PjitFunction(f)", 40, 45),
        ("shard_args", 55, 58)]
# the gaps (5,12) (22,25) (35,40) (50,70) (80,90) (95,120), cut by span
WANT = {"cd.sweep": (5 + 10 + 10 + 5, 4), "cd.coordinate": (2 + 5 + 10, 3),
        "no span": (20, 1), "cd.fetch": (3, 1)}


def _space() -> bytes:
    return (_plane("/device:TPU:0", [("XLA Ops", 1_000_000, DEVICE)])
            # the host's lines start 1 us earlier than the device's
            + _plane("/host:CPU", [("python3", 999_000, [
                (n, s0 + 1, s1 + 1) for n, s0, s1 in HOST]),
                ("tf_pjrt_thread", 999_000, [("wrapped_reduce", 1, 131)])]))


@pytest.fixture
def trace_file(tmp_path):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(_space())
    return str(path)


def test_gaps_go_to_the_innermost_span_and_add_up_to_the_idle_time(
        trace_file, capsys):
    assert trace_cli.main(["gaps", trace_file, "--json"]) == 0
    table = json.loads(capsys.readouterr().out)
    assert table["devices"] == 1 and table["gaps"] == 6
    assert table["window_s"] == pytest.approx(130e-6)
    assert table["busy_s"] == pytest.approx(60e-6)
    assert table["idle_s"] == pytest.approx(70e-6)
    got = {name: (round(r["idle_s"] * 1e6, 6), r["gaps"])
           for name, r in table["spans"].items()}
    assert got == WANT
    assert list(table["spans"]) == sorted(WANT, key=lambda n: -WANT[n][0])
    assert sum(r["share"] for r in table["spans"].values()) == (
        pytest.approx(1.0))


def test_gaps_prints_a_table_and_reads_a_gzipped_trace(
        trace_file, tmp_path, capsys):
    gz = str(tmp_path / "host.xplane.pb.gz")
    with open(trace_file, "rb") as src, gzip.open(gz, "wb") as dst:
        dst.write(src.read())
    assert trace_cli.main(["gaps", gz]) == 0
    out = capsys.readouterr().out
    assert "device idle 0.070 ms of a window of 0.130 ms" in out
    assert [ln.split()[0] for ln in out.splitlines()[3:]] == [
        "cd.sweep", "no", "cd.coordinate", "cd.fetch"]


def test_host_spans_are_the_span_shaped_events(trace_file, tmp_path):
    assert sorted(n for n, _, _ in xplane.host_spans(trace_file)) == [
        "cd.coordinate", "cd.fetch", "cd.sweep"]
    # one word is a span where it is the area of a dotted one
    path = tmp_path / "fit.xplane.pb"
    path.write_bytes(_plane("/host:CPU", [("python3", 0, [
        ("fit", 0, 10), ("fit.dispatch", 2, 4), ("shard_args", 3, 4)])]))
    assert sorted(n for n, _, _ in xplane.host_spans(str(path))) == [
        "fit", "fit.dispatch"]
    # the refactored device reader still reads the ops' line
    assert xplane.kernel_table(trace_file)["busy_s"] == pytest.approx(60e-6)


def test_a_trace_without_a_device_has_no_gaps(tmp_path, capsys):
    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(_plane("/host:CPU", [("python3", 0, HOST)]))
    assert trace_cli.main(["gaps", str(path), "--json"]) == 1
    assert json.loads(capsys.readouterr().out)["idle_s"] == 0
