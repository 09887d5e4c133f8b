"""The device trace by ``photon.*`` scope and its idle time by host span: what
``photon-trace kernels`` and ``photon-trace gaps`` print.

The JAX profiler's ``.xplane.pb`` holds, per device, a line ``XLA Ops`` with
one event per executed HLO instruction. The instruction's metadata carries
``tf_op`` — the jax name stack it was traced under
(``jit(photon_fit_lbfgs_margin)/while/body/photon.csc/boundary_combine/lp/gather``)
— beside ``bytes_accessed`` and ``flops``; ``jax.profiler.ProfileData`` shows
the events but not that metadata, so the file is read here from its wire
format (protobuf ``XSpace``; the field numbers below are
``tsl/profiler/protobuf/xplane.proto``'s), with nothing imported.

A control-flow instruction (``while``, ``conditional``, ``call``) spans the
instructions of its body, so time is counted on *leaf* events: those that hold
no other event of their line. An instruction belongs to the innermost
``photon.*`` scope of its name stack; a fusion has the name stack of the
instruction XLA took its metadata from.

The host's plane (``/host:CPU``) holds a line a thread. While a profiler
session is live every ``obs.trace.span`` is an annotation there, on the
device's clock (``docs/observability.md``, the annotation rule); an idle gap
of a device is put down to the innermost span that covers it.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["find_xplane", "device_ops", "host_spans", "scope_of",
           "kernel_table", "format_table", "gap_table", "format_gaps"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
UNSCOPED = "(no photon scope)"
NO_SPAN = "no span"
# the form of an ``obs.trace.span`` name, ``area.name`` (``cd.fetch``,
# ``re.solve.bucket``); the runtime's own host events carry ``::``, spaces,
# capitals, ``$`` or parentheses, or are one word (``shard_args``), and an
# XLA instruction has a numeric suffix (``fusion.12``)
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_-]*(\.[a-z][a-z0-9_-]*)+$")
# name-stack components that are control flow or a transformation, not a
# scope: they may stand between a photon scope and its sub-scope
_NOT_A_SCOPE = re.compile(
    r"^(while|body|cond|branch_\d+_fun|closed_call|core_call|remat|"
    r"checkpoint|custom_jvp_call|custom_vjp_call|custom_lin|pjit|"
    r"shard_map|scan|.*[()].*)$")


# -- protobuf wire format ----------------------------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire in (1, 2, 5):
            size = {1: 8, 5: 4}.get(wire)
            if size is None:
                size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield field, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _metadata_stats(buf, names: Dict[int, str]) -> dict:
    """The XStats of an event's metadata, by stat name. A ``ref_value``
    points at a stat-metadata name (an interned string)."""
    out = {}
    for field, _, value in _fields(buf):
        if field != 5:  # XEventMetadata.stats
            continue
        name = got = None
        for f, wire, v in _fields(value):
            if f == 1:
                name = names.get(v)
            elif f in (3, 4):  # uint64_value, int64_value
                got = _signed(v) if f == 4 else v
            elif f == 5:
                got = _text(v)
            elif f == 7:
                got = names.get(v, "")
        if name is not None and got is not None:
            out[name] = got
    return out


def _map_entry(buf):
    key = value = None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_name(buf) -> str:
    for f, _, v in _fields(buf):
        if f == 2:
            return _text(v)
    return ""


def find_xplane(path: str) -> str:
    """A ``.xplane.pb`` (or ``.gz`` of one), or the newest one under a
    profile directory (``<dir>/plugins/profile/<time>/*.xplane.pb``)."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return found[-1]
    return path


def _read(path: str) -> memoryview:
    path = find_xplane(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return memoryview(f.read())


def _plane_lines(plane) -> Iterator[Tuple[str, List[Tuple[dict, int, int]]]]:
    """(line name, [(event metadata, start_ps, end_ps)]) of one plane; the
    metadata is ``{"name", "tf_op", "bytes_accessed", "flops"}``, ``name``
    cut at the assignment (``fusion.79``) and at an annotation's arguments
    (``cd.fetch#what=model#``)."""
    lines, event_md, stat_names = [], {}, {}
    for f, _, v in _fields(plane):
        if f == 3:
            lines.append(v)
        elif f == 4:
            key, md = _map_entry(v)
            event_md[key] = md
        elif f == 5:
            key, md = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for g, _, x in _fields(md) if g == 2), "")
    decoded: Dict[int, dict] = {}

    def metadata(mid: int) -> dict:
        md = decoded.get(mid)
        if md is None:
            buf = event_md.get(mid, b"")
            name = next((_text(x) for g, _, x in _fields(buf) if g == 2), "")
            stats = _metadata_stats(buf, stat_names)
            md = decoded[mid] = {
                "name": name.split(" = ")[0].lstrip("%").split("#")[0],
                "tf_op": stats.get("tf_op", ""),
                "bytes_accessed": int(stats.get("bytes_accessed", 0)),
                "flops": int(stats.get("flops", 0))}
        return md

    for line in lines:
        name, t0_ns, events = "", 0, []
        for f, _, v in _fields(line):
            if f == 2:
                name = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        got = []
        for ev in events:
            mid = offset = dur = 0
            for f, _, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset = v
                elif f == 3:
                    dur = v
            start = t0_ns * 1000 + offset
            got.append((metadata(mid), start, start + dur))
        yield name, got


def device_ops(path: str) -> Dict[str, List[dict]]:
    """-> {device plane: [op]}: every event of the plane's ``XLA Ops``
    line as ``{"name", "start_ps", "end_ps", "tf_op", "bytes_accessed",
    "flops"}`` (``name`` cut at the assignment: ``fusion.79``)."""
    out: Dict[str, List[dict]] = {}
    for field, _, plane in _fields(_read(path)):
        if field != 1 or not DEVICE_PLANE.match(_plane_name(plane)):
            continue
        out[_plane_name(plane)] = [
            {**md, "start_ps": s0, "end_ps": s1}
            for name, events in _plane_lines(plane) if name == OPS_LINE
            for md, s0, s1 in events]
    return out


def host_spans(path: str) -> List[Tuple[str, int, int]]:
    """-> [(name, start_ps, end_ps)]: the host events whose name has a
    span's form (:data:`SPAN_NAME`), and those named by one word that is
    the area of such a span (``fit`` beside ``fit.dispatch``)."""
    events = []
    for field, _, plane in _fields(_read(path)):
        if field == 1 and _plane_name(plane) == HOST_PLANE:
            events.extend((md["name"], s0, s1)
                          for _, line in _plane_lines(plane)
                          for md, s0, s1 in line if s1 > s0)
    spans = [ev for ev in events if SPAN_NAME.match(ev[0])]
    areas = {name.split(".")[0] for name, _, _ in spans}
    return spans + [ev for ev in events if ev[0] in areas]


# -- from ops to scopes ------------------------------------------------------
def scope_of(tf_op: str) -> Optional[str]:
    """The innermost ``photon.*`` scope of a name stack, or None:
    ``jit(f)/while/body/photon.csc/boundary_combine/lp/gather`` ->
    ``photon.csc/boundary_combine/lp`` (the last component is the
    primitive; control-flow components in between are dropped)."""
    at = tf_op.rfind("photon.")
    if at < 0:
        return None
    parts = tf_op[at:].split("/")[:-1]
    kept = [p for p in parts if not _NOT_A_SCOPE.match(p)]
    return "/".join(kept) if kept else None


def _leaves(ops: List[dict]) -> List[dict]:
    """The events that hold no other event of their line (a core runs its
    ops one after the other, so to overlap is to nest)."""
    leaves, stack = [], []  # stack: [op, has_child]
    for op in sorted(ops, key=lambda o: (o["start_ps"], -o["end_ps"])):
        while stack and stack[-1][0]["end_ps"] <= op["start_ps"]:
            done, has_child = stack.pop()
            if not has_child:
                leaves.append(done)
        if stack and op["end_ps"] <= stack[-1][0]["end_ps"]:
            stack[-1][1] = True
        stack.append([op, False])
    leaves.extend(done for done, has_child in stack if not has_child)
    return leaves


def _new_row() -> dict:
    return {"ps": 0, "executions": 0, "bytes": 0, "by_name": defaultdict(int)}


def _add(row: dict, ps: int, executions: int, nbytes: int, by_name) -> None:
    row["ps"] += ps
    row["executions"] += executions
    row["bytes"] += nbytes
    for name, name_ps in by_name:
        row["by_name"][name] += name_ps


def kernel_table(path: str) -> dict:
    """-> ``{"busy_s", "attributed_share", "scopes": {scope: row},
    "rollup": {first two components: row}}`` over all device planes; a row
    is ``{"device_s", "share", "executions", "bytes_per_s",
    "instructions": [[name, seconds], ...]}``, shares of busy time."""
    scopes: Dict[str, dict] = defaultdict(_new_row)
    for ops in device_ops(path).values():
        for op in _leaves(ops):
            dur = op["end_ps"] - op["start_ps"]
            _add(scopes[scope_of(op["tf_op"]) or UNSCOPED], dur, 1,
                 op["bytes_accessed"], [(op["name"], dur)])
    busy_ps = sum(r["ps"] for r in scopes.values())
    rollup: Dict[str, dict] = defaultdict(_new_row)
    for scope, r in scopes.items():
        if scope != UNSCOPED:
            _add(rollup["/".join(scope.split("/")[:2])], r["ps"],
                 r["executions"], r["bytes"], r["by_name"].items())

    def finish(rows: Dict[str, dict]) -> Dict[str, dict]:
        done = {}
        for scope, r in sorted(rows.items(), key=lambda kv: -kv[1]["ps"]):
            secs = r["ps"] * 1e-12
            done[scope] = {
                "device_s": secs,
                "share": r["ps"] / busy_ps if busy_ps else 0.0,
                "executions": r["executions"],
                "bytes_per_s": r["bytes"] / secs if secs else 0.0,
                "instructions": [
                    [n, ps * 1e-12] for n, ps in
                    sorted(r["by_name"].items(), key=lambda kv: -kv[1])]}
        return done

    unscoped = scopes[UNSCOPED]["ps"] if UNSCOPED in scopes else 0
    return {"busy_s": busy_ps * 1e-12,
            "attributed_share": 1 - unscoped / busy_ps if busy_ps else 0.0,
            "scopes": finish(scopes), "rollup": finish(rollup)}


def format_table(table: dict, instructions: int = 4) -> str:
    out = [f"device busy {table['busy_s']:.6f} s over all chips; "
           f"{100 * table['attributed_share']:.2f}% in photon.* scopes"]
    for title, rows in (("by scope (first two components)", table["rollup"]),
                        ("by innermost scope", table["scopes"])):
        out.append("")
        out.append(title)
        out.append(f"{'scope':<44} {'device s':>11} {'% busy':>7} "
                   f"{'execs':>8} {'GB/s':>8}  instructions")
        for scope, r in rows.items():
            names = ", ".join(n for n, _ in r["instructions"][:instructions])
            more = len(r["instructions"]) - instructions
            if more > 0:
                names += f", +{more}"
            out.append(f"{scope:<44} {r['device_s']:>11.6f} "
                       f"{100 * r['share']:>7.2f} {r['executions']:>8d} "
                       f"{r['bytes_per_s'] / 1e9:>8.2f}  {names}")
    return "\n".join(out)


# -- from idle gaps to host spans ---------------------------------------------
def _busy(ops: List[dict]) -> List[Tuple[int, int]]:
    """The union of a device's leaf-op intervals, in order."""
    merged: List[List[int]] = []
    for s0, s1 in sorted((op["start_ps"], op["end_ps"])
                         for op in _leaves(ops)):
        if merged and s0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s1)
        else:
            merged.append([s0, s1])
    return [(s0, s1) for s0, s1 in merged]


def _innermost(spans: List[Tuple[str, int, int]]
               ) -> List[Tuple[int, int, str]]:
    """The host's time as disjoint pieces ``(start, end, span)``, each under
    the innermost span that covers it: of those that cover it, the one that
    began last (on one thread spans nest, so that is the inner one; an
    equal start goes to the one that ends first). Time no span covers
    has no piece."""
    edges = sorted({t for _, s0, s1 in spans for t in (s0, s1)})
    starts = sorted(spans, key=lambda sp: sp[1])
    pieces, active, k = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        active = [sp for sp in active if sp[2] > t0]
        while k < len(starts) and starts[k][1] <= t0:
            if starts[k][2] > t0:
                active.append(starts[k])
            k += 1
        if active:
            name = max(active, key=lambda sp: (sp[1], -sp[2]))[0]
            if pieces and pieces[-1][2] == name and pieces[-1][1] == t0:
                pieces[-1] = (pieces[-1][0], t1, name)
            else:
                pieces.append((t0, t1, name))
    return pieces


def gap_table(ops_by_device: Dict[str, List[dict]],
              spans: List[Tuple[str, int, int]]) -> dict:
    """-> ``{"devices", "window_s", "busy_s", "idle_s", "gaps", "spans":
    {span: {"idle_s", "gaps", "share"}}}`` over all devices: each idle gap
    between a device's leaf ops (``device_ops``), from its first op to its
    last, is cut where the innermost covering host span (``host_spans``)
    changes and each part is put down to that span (:data:`NO_SPAN` where
    none covers it), so the rows partition the idle time. A gap counts once
    in each row it touches; shares are of the idle time."""
    pieces = _innermost(spans)
    rows: Dict[str, dict] = defaultdict(lambda: {"ps": 0, "gaps": 0})
    window = busy = n_gaps = 0
    for ops in ops_by_device.values():
        if not ops:
            continue
        busy_ps = _busy(ops)
        window += busy_ps[-1][1] - busy_ps[0][0]
        busy += sum(s1 - s0 for s0, s1 in busy_ps)
        gaps = [(a[1], b[0]) for a, b in zip(busy_ps, busy_ps[1:])]
        n_gaps += len(gaps)
        j = 0
        for g0, g1 in gaps:
            while j < len(pieces) and pieces[j][1] <= g0:
                j += 1
            touched, cur, k = set(), g0, j
            while cur < g1:
                if k < len(pieces) and pieces[k][0] < g1:
                    p0, p1, name = pieces[k]
                    if p0 > cur:
                        rows[NO_SPAN]["ps"] += p0 - cur
                        touched.add(NO_SPAN)
                    rows[name]["ps"] += min(p1, g1) - max(p0, cur)
                    touched.add(name)
                    cur = min(p1, g1)
                    k += 1
                else:
                    rows[NO_SPAN]["ps"] += g1 - cur
                    touched.add(NO_SPAN)
                    cur = g1
            for name in touched:
                rows[name]["gaps"] += 1
    idle = sum(r["ps"] for r in rows.values())
    return {"devices": sum(1 for ops in ops_by_device.values() if ops),
            "window_s": window * 1e-12, "busy_s": busy * 1e-12,
            "idle_s": idle * 1e-12, "gaps": n_gaps,
            "spans": {name: {"idle_s": r["ps"] * 1e-12, "gaps": r["gaps"],
                             "share": r["ps"] / idle if idle else 0.0}
                      for name, r in sorted(rows.items(),
                                            key=lambda kv: -kv[1]["ps"])}}


def format_gaps(table: dict) -> str:
    idle = table["idle_s"]
    out = [f"device idle {1e3 * idle:.3f} ms of a window of "
           f"{1e3 * table['window_s']:.3f} ms over {table['devices']} "
           f"device(s), in {table['gaps']} gaps; by the innermost host span "
           "that covers it",
           "",
           f"{'span':<32} {'idle ms':>12} {'gaps':>8} {'% idle':>7}"]
    for name, r in table["spans"].items():
        out.append(f"{name:<32} {1e3 * r['idle_s']:>12.3f} {r['gaps']:>8d} "
                   f"{100 * r['share']:>7.2f}")
    return "\n".join(out)
