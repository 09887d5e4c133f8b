"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy and idle time, the gaps between the pieces of the
window, collective time not hidden behind compute, the ops that took most
time and the longest idle gaps.

``load_events`` reads the file with ``jax.profiler.ProfileData`` into plain
tuples; ``reduce_events`` is arithmetic on those tuples alone, so it is
checked on hand-made events as well as on the recorded trace under
``benchmark/testdata/``.

A TPU's plane is named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
event per executed HLO op, named by the instruction's text
(``%psum.19 = f32[16777216] all-reduce(...)``): what an op is, is read from
the opcode, not from the name jax gave it. A control-flow op (``while``,
``conditional``, ``call``) spans the ops of its body, so busy time is the
union of the intervals of *leaf* ops: those that hold no other op of the
line. On several chips the first run of a program inside a trace comes
without its text (ops named ``region.<n>``, no event on ``XLA Modules``):
such ops count as busy, and stay out of the top ops and of the collective
share, which is taken over the runs that ``XLA Modules`` shows.
Its line ``XLA Modules`` holds one event per executed program: the pieces of
the window are the events of the program that took most time there.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_LINE = "python3"  # the interpreter's own spans (PjitFunction, np.asarray)
OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
COLLECTIVES = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                         r"all-to-all|collective-permute)(-start|-done)?$")


def load_events(path: str) -> dict:
    """-> {device: {"ops": [(name, start_ns, end_ns)], "modules": [...]}},
    and under the key ``"host"`` the host's spans, for labelling gaps."""
    from jax.profiler import ProfileData

    out: dict = {}
    host = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    dev[key].append((e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name != HOST_LINE:
                    continue
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)))
    out["host"] = host
    return out


def union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b, j=0):
    """Parts of merged intervals ``a`` not covered by merged ``b``; the
    first ``j`` of ``b`` are known to end at or before ``a`` starts."""
    out = []
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def opcode(name: str) -> str:
    """``%psum.19 = f32[8]{0:T(1024)} all-reduce(f32[8] %x), ...`` ->
    ``all-reduce``."""
    m = OPCODE.search(name.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def leaf_ops(ops):
    """The events that hold no other event of their line (the ops of one
    core run one after the other, so to overlap is to nest)."""
    leaves, stack = [], []  # stack: [event, has_child]
    for ev in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0][2] <= ev[1]:
            done, has_child = stack.pop()
            if not has_child:
                leaves.append(done)
        if stack and ev[2] <= stack[-1][0][2]:  # held whole: nested
            stack[-1][1] = True
        stack.append([ev, False])
    leaves.extend(done for done, has_child in stack if not has_child)
    return leaves


def op_label(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion.12``: the name the trace prints,
    cut at the assignment."""
    return name.split(" = ")[0].lstrip("%")


def _host_label(host, s, e):
    """The host span that covers most of the gap [s, e]."""
    best, best_cover = None, 0.0
    for name, hs, he in host:
        cover = min(e, he) - max(s, hs)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def _module_at(modules, t):
    """(index, name without the hash) of the program's run at time ``t``."""
    for i, (name, s, e) in enumerate(modules):
        if s <= t <= e:
            return i, name.split("(")[0]
    return None, "no program"


def sizes(events: dict) -> dict:
    """What a reduction has to read, on the first chip: the harness prints
    it beside the seconds the reduction took."""
    chips = [d for d, v in events.items() if d != "host" and v["ops"]]
    v = events[min(chips)] if chips else {"ops": [], "modules": []}
    return {"trace_device_ops": len(leaf_ops([o for o in v["ops"]
                                              if o[2] > o[1]])),
            "trace_host_spans": len(events.get("host", [])),
            "trace_programs": len(v["modules"])}


def reduce_events(events: dict) -> dict | None:
    """The summary the metric readers use; ``None`` where no op ran on a
    device. Seconds throughout. Every step is one pass over the window's
    ops, program runs or host spans, or a sort of them: a step that scans
    them once for each gap or each piece takes minutes at the 10^5 ops of a
    GLMix window, and grows with the square of any gain (PERF.md, PR 32)."""
    host = events.get("host", [])
    devices = {d: v for d, v in events.items() if d != "host" and v["ops"]}
    if not devices:
        return None
    busy, windows, exposed, shown, piece_gaps = [], [], [], [], []
    op_time = defaultdict(float)
    gaps = []
    has_collective = False
    for dev, v in sorted(devices.items()):
        leaves = [(n, opcode(n), s, e)
                  for n, s, e in leaf_ops([o for o in v["ops"] if o[2] > o[1]])]
        if not leaves:
            continue
        start = min(s for _, _, s, _ in leaves)
        end = max(e for _, _, _, e in leaves)
        merged = union((s, e) for _, _, s, e in leaves)
        busy.append(total(merged) / 1e9)
        windows.append((end - start) / 1e9)
        for n, op, s, e in leaves:
            if op:  # an op that came with its text
                op_time[op_label(n)] += (e - s) / 1e9 / len(devices)
        coll = union((s, e) for _, op, s, e in leaves if COLLECTIVES.match(op))
        rest = union((s, e) for _, op, s, e in leaves
                     if not COLLECTIVES.match(op))
        # the window's pieces: runs of the program that took most time
        by_module = defaultdict(float)
        for n, s, e in v["modules"]:
            by_module[n] += e - s
        pieces = []
        if by_module:
            main = max(by_module, key=by_module.get)
            pieces = sorted([s, e] for n, s, e in v["modules"] if n == main)
            ends = [e for _, e in merged]
            for (s0, e0), (s1, e1) in zip(pieces, pieces[1:]):
                idle = total(subtract([[e0, s1]], merged,
                                      bisect_right(ends, e0)))
                piece_gaps.append(idle / 1e9)
        if coll and pieces:
            has_collective = True
            alone = subtract(coll, rest)
            exposed.append(total(subtract(alone, subtract(
                [[start, end]], pieces))) / 1e9)
            shown.append(total(pieces) / 1e9)
        if dev == min(devices):  # the longest idle gaps, on the first chip
            # ranked before they are labelled: a label scans every program
            # run and every host span, and ten are printed. Ranked by the
            # seconds that are printed, so that gaps which round to the same
            # seconds tie, and ties keep their order in time (a stable sort)
            between = sorted(((e0, s1) for (_, e0), (s1, _)
                              in zip(merged, merged[1:])),
                             key=lambda g: -((g[1] - g[0]) / 1e9))
            for e0, s1 in between[:10]:
                (i0, before), (i1, after) = (
                    _module_at(v["modules"], e0 - 1),
                    _module_at(v["modules"], s1 + 1))
                where = (f"inside {before}" if i0 == i1
                         else f"between {before} and {after}")
                label = _host_label(host, e0, s1)
                gaps.append([where + (f", host in {label}" if label else ""),
                             (s1 - e0) / 1e9])
    if not busy:
        return None
    n = len(busy)
    return {
        "busy_s": sum(busy) / n,
        "window_s": sum(windows) / n,
        "devices": n,
        "top_ops": [[k, v] for k, v in sorted(op_time.items(),
                                              key=lambda kv: -kv[1])[:10]],
        "top_gaps": gaps,
        "piece_gaps_s": piece_gaps,
        # over the runs that came with their text, mean over the chips
        "collective_exposed_s": (sum(exposed) / len(exposed)
                                 if has_collective else None),
        "collective_window_s": (sum(shown) / len(shown)
                                if has_collective else None),
    }
