"""What every traffic kind shares: the closed loop, the sample of answers
kept for the check, the tail.

A traffic mix is a data file, ``traffic/<mix>.json``.  Its ``kind``
names the generator that drives it, ``kinds/<kind>.py``, found by name;
the other keys are that generator's parameters.  Every kind reads

* ``aggs``: the aggregate bundle, ``{name: [kind]}`` or
  ``{name: [kind, "relation.column"]}``, over the deployment's query;
  each aggregate kind is ``aggregates/<kind>.py``, found by name;
* ``warmup``: requests run in set-up, before the window;
* ``check_sample``: how many of the window's answers are kept, drawn
  from the seed, and compared with the reference afterwards.

A kind's ``make(mix, ctx)`` returns the loop that set-up warms
(``warm()``) and the window drives (``window(seconds, span)``); after
the window the harness reads its ``latencies``, ``errors``,
``answers()`` (``(tables, answer)`` pairs for the check) and
``end_to_end()`` (its end-to-end metrics by name).  Its ``span_name``
names the host span of each request, which the trace reduction reads.  ``ctx`` carries the
``server``, the ``query``, the cell's ``spec``, the generated
``tables`` and the ``seed``.
"""
from __future__ import annotations

import time

import numpy as np


class Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k, self.items, self.seen = k, [], 0
        self._rng = np.random.default_rng([seed, 2])

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self._rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def p95_ms(latencies: list[float]) -> float:
    return float(np.percentile(np.asarray(latencies) * 1e3, 95))


class ClosedLoop:
    """One client: the next request is sent once the previous one has
    returned.  The window closes when the request in flight at
    ``seconds`` returns, so rates are taken over all the work and all the
    time of the window.  Subclasses give ``request()`` and
    ``keep(answer)``."""

    span_name = "request"

    def __init__(self, mix):
        self.mix = mix
        self.latencies: list[float] = []
        self.errors: list[str] = []
        self.elapsed = 0.0

    def warm(self) -> None:
        for _ in range(self.mix["warmup"]):
            self.request()

    def window(self, seconds: float, span) -> None:
        t0 = t_done = time.perf_counter()
        while t_done - t0 < seconds:
            t = time.perf_counter()
            try:
                with span(self.span_name):
                    answer = self.request()
            except Exception as e:  # a failed request counts, the loop goes on
                self.errors.append(f"{type(e).__name__}: {e}")
                answer = None
            t_done = time.perf_counter()
            self.latencies.append(t_done - t)
            self.keep(answer)
        self.elapsed = t_done - t0

    def keep(self, answer) -> None:
        pass
