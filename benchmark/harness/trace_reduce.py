"""From a profiler trace (``*.xplane.pb``) to the numbers the metrics read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. The
arithmetic is on plain ``(start, end)`` intervals so it can be checked by
hand (``benchmark/tests/test_trace_reduce.py``):

- *busy* is the union of the intervals in which an operation ran on one
  chip; *idle share* is 1 - busy over the traced window of that chip
  (first operation's start to last operation's end);
- an operation's *self time* is its duration minus what its nested
  children cover (a ``while`` spans its body), so totals add up;
- *collective time* is the union of all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute intervals (an async
  ``-start``/``-done`` pair counts from the start's begin to the done's
  end); *exposed* is the part of it in which no other leaf operation runs;
- an idle gap is labelled by the benchmark's own host span
  (``jax.profiler.TraceAnnotation("bench:...")``) that overlaps it most.

On a v5e an event of the ``XLA Ops`` line is named by the whole text of
its HLO instruction and carries no other metadata, so a Mosaic call is
known by ``custom_call_target="tpu_custom_call"`` in that text and told
from another by its result shapes (no ``pallas_call`` of the program
sets ``name=``); ``short_name`` cuts the text down for the breakdown.

Run as a script to look at a trace by hand:
``python benchmark/harness/trace_reduce.py <file-or-dir>``.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Any, Callable, Iterable, Sequence

Interval = tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench:"
#: how XLA:TPU marks a Mosaic (Pallas) kernel call in an operation's text
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
_ASYNC = re.compile(r"^(?P<kind>.+?)-(?P<half>start|done)(?P<id>(\.\d+)?)$")
_INSTRUCTION = re.compile(r"^%?(?P<name>[^\s=]+)")


# -- interval arithmetic -----------------------------------------------------


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Sorted disjoint intervals covering the same points."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> list[Interval]:
    """The part of ``a`` that no interval of ``b`` covers."""
    out: list[Interval] = []
    cover = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in cover:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval]) -> list[Interval]:
    """The holes between the (already disjoint, sorted) busy intervals."""
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def self_times(events: Sequence[tuple[float, float, Any]]) -> list[tuple[Any, float, bool]]:
    """``(key, self_seconds, is_leaf)`` per event of one line, where an
    event nested inside another takes its time out of the parent."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -(events[i][1] - events[i][0])))
    child_cover = [0.0] * len(events)
    has_child = [False] * len(events)
    stack: list[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            child_cover[stack[-1]] += e - s
            has_child[stack[-1]] = True
        stack.append(i)
    return [(events[i][2], max(0.0, events[i][1] - events[i][0] - child_cover[i]), not has_child[i])
            for i in range(len(events))]


# -- reading the file ---------------------------------------------------------


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return files[-1]


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(path))


_SHORT = re.compile(r"^%?(?P<name>\S+) = \(?(?P<shape>[a-z0-9]+\[[^\]]*\])")
_OPCODE = re.compile(r"[\}\)\]] (?P<op>[a-z][a-z\-]*)\(")


def short_name(text: str) -> str:
    """``%fusion.1 = bf16[8,128]{...} fusion(...)`` -> ``fusion.1 bf16[8,128] fusion``."""
    m = _SHORT.match(text)
    if not m:
        return text[:120]
    op = _OPCODE.search(text, m.end("shape"))
    out = f"{m.group('name')} {m.group('shape')}"
    if op:
        out += f" {op.group('op')}"
    return out + (" [mosaic]" if MOSAIC_CALL in text else "")


def instruction(text: str) -> str:
    """The instruction's own name: an operand such as ``%all-gather.31`` in
    a fusion's text must not make the fusion a collective."""
    return _INSTRUCTION.match(text).group("name")


def reduce_profile(pd, *, top: int = 10) -> dict[str, Any] | None:
    """Reduce a ``ProfileData`` to a plain dict, or None when no device
    plane holds an operation (a CPU trace)."""
    host_spans: list[tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        host_spans.append((s, s + ev.duration_ns * 1e-9, ev.name[len(HOST_SPAN_PREFIX):]))
    per_device: dict[int, dict[str, Any]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        line = next((ln for ln in plane.lines if ln.name == OPS_LINE), None) if m else None
        if line is None:
            continue
        events: list[tuple[float, float, str]] = []
        for ev in line.events:
            s = ev.start_ns * 1e-9
            events.append((s, s + ev.duration_ns * 1e-9, ev.name))
        if events:
            per_device[int(m.group(1))] = _reduce_device(events, top)
    if not per_device:
        return None
    for dev in per_device.values():
        dev["idle_gaps"] = _label_gaps(dev.pop("_gaps"), host_spans, top)
    first = per_device[min(per_device)]
    n = len(per_device)
    return {
        "devices": sorted(per_device),
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "window_s": sum(d["window_s"] for d in per_device.values()) / n,
        "per_device": {str(k): {"busy_s": v["busy_s"], "window_s": v["window_s"]}
                       for k, v in per_device.items()},
        # per-chip detail from the lowest-numbered chip: in SPMD every chip
        # runs the same program, and summing chips would multiply the times
        "chip": min(per_device),
        "idle_pct": 100.0 * (1.0 - first["busy_s"] / first["window_s"]),
        "ops": first["ops"],
        "device_ops": first["device_ops"],
        "idle_gaps": first["idle_gaps"],
        "collective_s": first["collective_s"],
        "collective_exposed_s": first["collective_exposed_s"],
        "host_spans": _span_totals(host_spans),
    }


def _collective_intervals(events: Sequence[tuple[float, float, str]]) -> list[Interval]:
    out: list[Interval] = []
    starts: dict[tuple[str, str], list[float]] = {}
    for s, e, text in sorted(events):
        name = instruction(text)
        if not COLLECTIVE.match(name):
            continue
        m = _ASYNC.match(name)
        if m and m.group("half") == "start":
            starts.setdefault((m.group("kind"), m.group("id")), []).append(s)
        elif m and m.group("half") == "done":
            pending = starts.get((m.group("kind"), m.group("id")))
            out.append((pending.pop(0), e) if pending else (s, e))
        else:
            out.append((s, e))
    return out


def _reduce_device(events, top) -> dict[str, Any]:
    busy = union((s, e) for s, e, _ in events)
    window = busy[-1][1] - busy[0][0]
    ops: dict[str, dict[str, Any]] = {}
    leaves: list[tuple[float, float, str]] = []
    for (s, e, name), (_, self_s, leaf) in zip(events, self_times(events)):
        row = ops.setdefault(name, {"self_s": 0.0, "total_s": 0.0, "count": 0})
        row["self_s"] += self_s
        row["total_s"] += e - s
        row["count"] += 1
        if leaf:
            leaves.append((s, e, name))
    coll = _collective_intervals(events)
    others = [(s, e) for s, e, name in leaves if not COLLECTIVE.match(instruction(name))]
    ranked = sorted(ops.items(), key=lambda kv: -kv[1]["self_s"])
    return {
        "busy_s": sum(e - s for s, e in busy),
        "window_s": window,
        "ops": ops,
        "device_ops": [[short_name(name), row["self_s"]] for name, row in ranked[:top]],
        "_gaps": gaps(busy),
        "collective_s": measure(coll),
        "collective_exposed_s": measure(subtract(coll, others)),
    }


def _label_gaps(holes: Sequence[Interval], host_spans, top: int) -> list[list[Any]]:
    """The idle seconds by what the host was doing, longest first: each
    gap goes to the benchmark's host span that overlaps it most."""
    by_label: dict[str, float] = {}
    for s, e in holes:
        best, best_overlap = "unattributed", 0.0
        for hs, he, label in host_spans:
            overlap = min(e, he) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = label, overlap
        by_label[best] = by_label.get(best, 0.0) + (e - s)
    longest = max(holes, key=lambda g: g[1] - g[0], default=None)
    rows = sorted(by_label.items(), key=lambda kv: -kv[1])[: top - 1]
    out = [[f"during {label}", seconds] for label, seconds in rows]
    if longest is not None:
        out.append(["longest single gap", longest[1] - longest[0]])
    return out


def _span_totals(host_spans) -> dict[str, dict[str, float]]:
    out: dict[str, dict[str, float]] = {}
    for s, e, label in host_spans:
        row = out.setdefault(label, {"seconds": 0.0, "count": 0})
        row["seconds"] += e - s
        row["count"] += 1
    return out


def reduce_trace(path: str, *, top: int = 10) -> dict[str, Any] | None:
    return reduce_profile(_load(path), top=top)


def kernel_seconds(reduced: dict[str, Any], belongs: Callable[[str], bool]) -> tuple[float, int]:
    """Total self time and number of calls of the operations on the
    reported chip whose text ``belongs`` accepts."""
    seconds, calls = 0.0, 0
    for text, row in reduced["ops"].items():
        if belongs(text):
            seconds += row["self_s"]
            calls += row["count"]
    return seconds, calls


def describe(path: str, limit: int = 40) -> str:
    """What a person needs to see before writing code against a trace:
    planes, lines, and the heaviest events with their metadata."""
    pd = _load(path)
    out = [f"trace: {find_xplane(path)}"]
    for plane in pd.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r} stats={dict(list(plane.stats)[:6])} lines={len(lines)}")
        for line in lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            if not (DEVICE_PLANE.match(plane.name) or any(
                    ev.name.startswith(HOST_SPAN_PREFIX) for ev in events[:2000])):
                continue
            totals: dict[str, list[Any]] = {}
            for ev in events:
                row = totals.setdefault(ev.name, [0.0, 0, ev])
                row[0] += ev.duration_ns
                row[1] += 1
            for name, (ns, count, ev) in sorted(totals.items(), key=lambda kv: -kv[1][0])[:limit]:
                stats = " ".join(f"{k}={str(v)[:80]}" for k, v in ev.stats if not str(k).startswith("device_"))
                out.append(f"    {ns * 1e-6:10.3f} ms x{count:<6} {short_name(name)} | {stats} | {name[:400]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
