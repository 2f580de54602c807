"""The start-up clock: when the process began and what its imports cost.

Imported first by ``hops_tpu/__init__.py``, before anything that could
be slow, so it is stdlib-only and does nothing but read the clock. A
package whose own import is worth a span wraps its import block::

    with _startup.importing("hops_tpu.models"):
        from hops_tpu.models.cnn import ...

Finished imports wait in :data:`pending` until ``telemetry/spans.py``
is loaded and sets :data:`sink`; from then on each goes to the sink as
it ends. The sink records them as ``hops_tpu_startup_import`` spans
under the process root, a nested import as a child of the import that
caused it. With ``HOPS_TPU_TRACING=0`` an import block costs one
attribute test.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable

#: ``HOPS_TPU_TRACING`` as the process was started with it: what
#: ``telemetry/tracing.py`` takes for its own switch.
TRACING_AT_START = os.environ.get("HOPS_TPU_TRACING", "1") not in ("0", "false", "")

#: The first instant this package knows of: what stands in for the
#: process's start where ``/proc`` cannot say.
FIRST_IMPORT = time.time()

#: Imports that ended before a tracer was there to take them, oldest first.
pending: list["importing"] = []
#: Set by ``telemetry/spans.py`` once it is importable: takes one
#: finished :class:`importing` and records it.
sink: Callable[["importing"], Any] | None = None

_stack = threading.local()  # .open: the imports under way on this thread
_process_start: float | None = None  # read once: every reader gets the same instant


def process_start() -> float:
    """Wall-clock time at which the kernel started this process (from
    ``/proc/self/stat`` and ``/proc/uptime``, to a clock tick), or
    :data:`FIRST_IMPORT` where ``/proc`` is missing or disagrees with
    the clock."""
    global _process_start
    if _process_start is None:
        _process_start = _read_process_start()
    return _process_start


def _read_process_start() -> float:
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command's closing parenthesis; starttime is the 22nd overall
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")  # both count from the boot
        started = time.time() - age
    except (OSError, ValueError, IndexError):
        return FIRST_IMPORT
    # a container whose /proc counts from another boot than its clock gives a start in the future
    return started if started <= FIRST_IMPORT else FIRST_IMPORT


class importing:
    """One package's import, timed on the wall clock: a context manager
    round the package's import block."""

    __slots__ = ("package", "span_id", "parent", "start", "end")

    def __init__(self, package: str):
        self.package = package
        self.span_id = self.parent = self.start = self.end = None

    def __enter__(self) -> "importing":
        if not TRACING_AT_START:
            return self
        open_ = _stack.__dict__.setdefault("open", [])
        self.parent = open_[-1] if open_ else None
        self.span_id = os.urandom(8).hex()
        open_.append(self)
        self.start = time.time()
        return self

    def __exit__(self, *exc: Any) -> None:
        if self.start is None:
            return
        self.end = time.time()
        _stack.open.pop()
        if sink is not None:
            sink(self)
        else:
            pending.append(self)
