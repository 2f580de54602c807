"""Open-loop load: a pacer thread holds the schedule, a pool does the work.

Copied in shape from ``hops_tpu/telemetry/workload/replay.py``: the
pacer sleeps until each request's due instant and hands it to a worker,
so a slow response never delays a later arrival. Every request is timed
from its DUE instant, and how late the generator ran is kept per request.
"""

from __future__ import annotations

import concurrent.futures
import time
from typing import Any, Callable, Sequence


def run_open_loop(
    due_offsets_s: Sequence[float],
    send: Callable[[int], dict[str, Any]],
    *,
    workers: int,
    clock: Callable[[], float] = time.perf_counter,
    sleep: Callable[[float], None] = time.sleep,
) -> tuple[float, list[dict[str, Any]]]:
    """Issue request ``i`` at ``t0 + due_offsets_s[i]``; return ``(t0,
    records)``. ``send(i)`` does the request and returns a dict; the
    record adds ``i``, ``due`` (absolute), ``sent`` and ``received``.
    A ``send`` that raises is a record with ``error``."""
    records: list[dict[str, Any] | None] = [None] * len(due_offsets_s)

    def issue(i: int, due: float) -> None:
        sent = clock()
        try:
            row = send(i)
        except Exception as e:  # noqa: BLE001 — a failed request is a data point
            row = {"error": f"{type(e).__name__}: {e}"}
        row.setdefault("received", clock())  # a send that does more after the answer stamps it itself
        row.update(i=i, due=due, sent=sent)
        records[i] = row

    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix="bench-client")
    futures = []
    t0 = clock()
    try:
        for i, offset in enumerate(due_offsets_s):
            delay = t0 + offset - clock()
            if delay > 0:
                sleep(delay)
            futures.append(pool.submit(issue, i, t0 + offset))
    finally:
        pool.shutdown(wait=True)
    for f in futures:
        f.result()
    return t0, [r for r in records if r is not None]
