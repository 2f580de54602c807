"""Which part of the training step a device operation belongs to.

The program enters ``jax.named_scope`` names inside itself
(``hops_tpu/telemetry/spans.py:TRAIN_SCOPES``, repeated in ``SCOPES``
here: a reader imports nothing from the program) and XLA carries them to
the profiler: in an ``*.xplane.pb`` every device event's *event
metadata* holds ``tf_op``, JAX's ``op_name`` of the instruction
(``jit(train_step)/transpose(jvp(TransformerLM))/block_0/attn/flash_bwd_dq/pallas_call``),
beside ``source``, ``hlo_category``, ``flops`` and ``bytes_accessed``.
``jax.profiler.ProfileData`` exposes an event's own stats only, so the
two tables that hold them (a plane's stat metadata and event metadata)
are read here straight from the protobuf wire format. The planes' lines,
where the events are, are skipped by their length: no event is visited,
and a trace of 0.7 GB costs its file read and nothing more.

The join is on the event's name (the whole HLO text, which is also the
key of ``trace_reduce``'s ``ops`` table), so the self times are the ones
the other metrics use. An operation belongs to the innermost vocabulary
scope of its ``tf_op``; a fusion has one ``tf_op`` and is counted once.

Run as a script to see a trace's operations by scope:
``python benchmark/harness/trace_scopes.py <file-or-dir>``.
"""

from __future__ import annotations

import mmap
import sys
from pathlib import Path
from typing import Any, Iterator

if __package__ in (None, ""):  # run as a script: the repo's root is not on the path yet
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.harness import trace_reduce  # noqa: E402

#: the program's device vocabulary, outermost first in a typical ``tf_op``
SCOPES = ("attn", "mlp", "embed", "final_norm", "lm_head_loss", "optimizer", "grad_exchange")
UNATTRIBUTED = "unattributed"
#: the stats of an event's metadata that are kept
KEPT_STATS = ("tf_op", "source")

# -- the protobuf wire format, as far as XSpace needs it ----------------------
# XSpace{1: planes}; XPlane{2: name, 3: lines, 4: event_metadata map, 5: stat_metadata map};
# map entry{1: key, 2: value}; XEventMetadata{2: name, 5: stats}; XStatMetadata{2: name};
# XStat{1: metadata_id, 5: str_value, 7: ref_value (a stat-metadata id whose name is the value)}.

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf, pos: int, end: int) -> Iterator[tuple[int, int, int, int]]:
    """``(field, wire type, value or start, end)`` of each field of the
    message in ``buf[pos:end]``; a length-delimited field is not read,
    only stepped over."""
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, pos = _varint(buf, pos)
            yield field, wire, value, pos
        elif wire == _BYTES:
            size, pos = _varint(buf, pos)
            yield field, wire, pos, pos + size
            pos += size
        elif wire in (_FIXED64, _FIXED32):
            size = 8 if wire == _FIXED64 else 4
            yield field, wire, pos, pos + size
            pos += size
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an XSpace")


def text_of(buf, start: int, end: int) -> str:
    return bytes(buf[start:end]).decode("utf-8", "replace")


def _map_entry(buf, start: int, end: int) -> tuple[int, int, int]:
    key, vstart, vend = 0, start, start
    for field, wire, a, b in fields(buf, start, end):
        if field == 1 and wire == _VARINT:
            key = a
        elif field == 2 and wire == _BYTES:
            vstart, vend = a, b
    return key, vstart, vend


def _plane_tables(buf, start: int, end: int) -> tuple[str, dict[str, dict[str, str]] | None]:
    """A plane's name and, for a device plane (None for any other),
    ``event name -> {stat name: value}`` for the kept stats of its event
    metadata."""
    name = ""
    stat_names: dict[int, str] = {}
    event_spans: list[tuple[int, int]] = []
    for field, wire, a, b in fields(buf, start, end):
        if wire != _BYTES:
            continue
        if field == 2:
            name = text_of(buf, a, b)
        elif field == 5:
            key, vs, ve = _map_entry(buf, a, b)
            for f2, w2, a2, b2 in fields(buf, vs, ve):
                if f2 == 2 and w2 == _BYTES:
                    stat_names[key] = text_of(buf, a2, b2)
        elif field == 4:
            event_spans.append(_map_entry(buf, a, b)[1:])
    if not trace_reduce.DEVICE_PLANE.match(name):
        return name, None
    events: dict[str, dict[str, str]] = {}
    kept = {key for key, stat in stat_names.items() if stat in KEPT_STATS}
    for vs, ve in event_spans:
        event_name, stats = "", {}
        for f2, w2, a2, b2 in fields(buf, vs, ve):
            if w2 != _BYTES:
                continue
            if f2 == 2:
                event_name = text_of(buf, a2, b2)
            elif f2 == 5:
                stat_id, value = 0, None
                for f3, w3, a3, b3 in fields(buf, a2, b2):
                    if f3 == 1 and w3 == _VARINT:
                        stat_id = a3
                    elif f3 == 5 and w3 == _BYTES:
                        value = text_of(buf, a3, b3)
                    elif f3 == 7 and w3 == _VARINT:
                        value = stat_names.get(a3)
                if stat_id in kept and value:
                    stats[stat_names[stat_id]] = value
        # one HLO text can have two entries (an async copy and its display
        # twin): the one that names a tf_op wins
        if event_name and (event_name not in events or "tf_op" in stats):
            events[event_name] = stats
    return name, events


def read_tables(path: str) -> dict[str, dict[str, dict[str, str]]]:
    """``plane name -> event name -> {"tf_op": ..., "source": ...}`` for
    every device plane of the trace at ``path`` (a file or a directory
    holding one ``*.xplane.pb``)."""
    out: dict[str, dict[str, dict[str, str]]] = {}
    with open(trace_reduce.find_xplane(path), "rb") as f, \
            mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        for field, wire, a, b in fields(buf, 0, len(buf)):
            if field == 1 and wire == _BYTES:
                name, events = _plane_tables(buf, a, b)
                if events is not None:
                    out[name] = events
    return out


# -- from tf_op to scope -------------------------------------------------------


def scope_of(tf_op: str | None) -> str | None:
    """The innermost vocabulary scope of an ``op_name``, or None. JAX
    wraps a scope that encloses a differentiated call in its transforms,
    so ``transpose(jvp(lm_head_loss))`` is ``lm_head_loss``."""
    if not tf_op:
        return None
    for part in reversed(tf_op.rstrip(":").split("/")):
        inner = part.rsplit("(", 1)[-1].rstrip(")")
        if inner in SCOPES:
            return inner
    return None


def by_scope(ops: dict[str, dict[str, Any]], events: dict[str, dict[str, str]]) -> dict[str, Any]:
    """Self seconds of ``ops`` (``trace_reduce``'s table of one chip) per
    scope, the rest under ``unattributed`` with its heaviest operations."""
    seconds = dict.fromkeys(SCOPES + (UNATTRIBUTED,), 0.0)
    outside: list[tuple[float, str, str]] = []
    for text, row in ops.items():
        tf_op = events.get(text, {}).get("tf_op")
        scope = scope_of(tf_op)
        seconds[scope or UNATTRIBUTED] += row["self_s"]
        if scope is None:
            outside.append((row["self_s"], trace_reduce.short_name(text), (tf_op or "").rstrip(":")))
    outside.sort(reverse=True)
    return {"seconds": seconds, "total_s": sum(seconds.values()),
            "heaviest_unattributed": [[name, tf_op, s] for s, name, tf_op in outside[:10]]}


def scopes_of_run(run: dict[str, Any], bench_dir: Path) -> dict[str, Any] | None:
    """The traced slice of ``run`` by scope (kept on ``run["trace"]`` so
    that five metrics read the file once), or None when the run has no
    device trace or no operation of it names a scope."""
    trace = run.get("trace")
    if not trace or not trace.get("steps") or not trace.get("ops"):
        return None
    if "scopes" not in trace:
        trace_dir = bench_dir / ".cache" / "trace" / run["workload"]
        try:
            tables = read_tables(str(trace_dir))
        except (FileNotFoundError, ValueError, IndexError) as e:
            print(f"benchmark: no scope tables from {trace_dir}: {e}", file=sys.stderr)
            tables = {}
        events = tables.get(f"/device:TPU:{trace['chip']}", {})
        scoped = by_scope(trace["ops"], events)
        named = scoped["total_s"] - scoped["seconds"][UNATTRIBUTED]
        trace["scopes"] = scoped if named > 0 else None
    return trace["scopes"]


def ms_per_step(run: dict[str, Any], bench_dir: Path, scope: str) -> float | None:
    """Device self time under ``scope`` per traced step, ms; None when
    nothing ran under it (a program that does not enter the scope, as the
    parent of the PR that brought ``lm_head_loss`` and ``optimizer``)."""
    scoped = scopes_of_run(run, bench_dir)
    if scoped is None or not scoped["seconds"][scope]:
        return None
    return 1e3 * scoped["seconds"][scope] / run["trace"]["steps"]


def describe(path: str, limit: int = 40) -> str:
    """A trace by scope, then its heaviest operations with their scope
    and ``tf_op``: what a person reads before trusting the metrics."""
    reduced = trace_reduce.reduce_trace(path, top=10)
    events = read_tables(path)[f"/device:TPU:{reduced['chip']}"]
    shown = by_scope(reduced["ops"], events)
    out = [f"trace: {trace_reduce.find_xplane(path)}"]
    backward = dict.fromkeys(shown["seconds"], 0.0)  # the part traced under transpose(...)
    kernels = dict.fromkeys(shown["seconds"], 0.0)  # the part in Mosaic calls
    for text, row in reduced["ops"].items():
        tf_op = events.get(text, {}).get("tf_op", "")
        scope = scope_of(tf_op) or UNATTRIBUTED
        backward[scope] += row["self_s"] * ("transpose(" in tf_op)
        kernels[scope] += row["self_s"] * (trace_reduce.MOSAIC_CALL in text)
    for scope, s in shown["seconds"].items():
        out.append(f"{1e3 * s:10.3f} ms  {100 * s / shown['total_s']:5.1f} %  {scope:13} "
                   f"backward {1e3 * backward[scope]:.3f}  kernels {1e3 * kernels[scope]:.3f}")
    out.append(f"{1e3 * shown['total_s']:10.3f} ms  self time of all operations; "
               f"busy {1e3 * reduced['busy_s']:.3f} ms of a window of {1e3 * reduced['window_s']:.3f} ms")
    for text, row in sorted(reduced["ops"].items(), key=lambda kv: -kv[1]["self_s"])[:limit]:
        tf_op = events.get(text, {}).get("tf_op", "").rstrip(":")
        out.append(f"{1e3 * row['self_s']:10.3f} ms x{row['count']:<5} {scope_of(tf_op) or '-':13} "
                   f"{trace_reduce.short_name(text)} | {tf_op}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
