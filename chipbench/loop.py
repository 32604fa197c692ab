"""The measured window: a closed loop with one client.

The client sends its next request when the previous one returns.  Each
request is timed from the call to its return.  The window ends with the
last request that started before ``seconds`` had passed, so it holds
whole requests only: its length runs from the first call to that
request's return.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Iterable


@dataclasses.dataclass
class Record:
    request: object
    result: object
    start: float
    end: float
    error: str | None = None

    @property
    def latency(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Window:
    records: list[Record]
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


def drive(compile_one: Callable, requests: Iterable, seconds: float, *,
          annotate: Callable | None = None,
          clock: Callable[[], float] = time.perf_counter) -> Window:
    """Send ``requests`` one after another through ``compile_one`` until
    ``seconds`` have passed; ``annotate(request)`` (a context manager)
    wraps each call, for the traced run's host spans."""
    records: list[Record] = []
    t0 = clock()
    deadline = t0 + seconds
    for req in requests:
        if records and clock() >= deadline:
            break
        span = annotate(req) if annotate else contextlib.nullcontext()
        start = clock()
        try:
            with span:
                result, error = compile_one(req), None
        except Exception as exc:          # a failed request is counted
            result, error = None, f"{type(exc).__name__}: {exc}"
        records.append(Record(req, result, start, clock(), error))
    return Window(records, t0, records[-1].end if records else clock())
