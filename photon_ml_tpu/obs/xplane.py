"""The device trace by ``photon.*`` scope: what ``photon-trace kernels`` prints.

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
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["find_xplane", "device_ops", "scope_of", "kernel_table",
           "format_table"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
UNSCOPED = "(no photon scope)"
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


def device_ops(path: str) -> Dict[str, List[dict]]:
    """-> {device plane: [op]}: every event of the plane's ``XLA Ops``
    line as ``{"name", "start_ps", "end_ps", "tf_op", "bytes_accessed",
    "flops"}`` (``name`` cut at the assignment: ``fusion.79``)."""
    path = find_xplane(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, List[dict]] = {}
    for field, _, plane in _fields(space):
        if field != 1 or not DEVICE_PLANE.match(_plane_name(plane)):
            continue
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
                name = next((_text(x) for g, _, x in _fields(buf) if g == 2),
                            "")
                stats = _metadata_stats(buf, stat_names)
                md = decoded[mid] = {
                    "name": name.split(" = ")[0].lstrip("%"),
                    "tf_op": stats.get("tf_op", ""),
                    "bytes_accessed": int(stats.get("bytes_accessed", 0)),
                    "flops": int(stats.get("flops", 0))}
            return md

        ops = []
        for line in lines:
            name, t0_ns, events = "", 0, []
            for f, _, v in _fields(line):
                if f == 2:
                    name = _text(v)
                elif f == 3:
                    t0_ns = v
                elif f == 4:
                    events.append(v)
            if name != OPS_LINE:
                continue
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
                ops.append({**metadata(mid), "start_ps": start,
                            "end_ps": start + dur})
        out[_plane_name(plane)] = ops
    return out


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
