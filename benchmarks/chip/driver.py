"""Drives the serving engine with the benchmark's traffic.

``Engine.run`` returns when the engine drains, and ``Engine.queue`` is
read on every loop iteration, so no other thread may submit. Arrivals
are therefore injected on the engine's own thread, once per loop
iteration, from a hook at the top of the iteration; when the engine
drains, the loop sleeps until the next due time and calls ``run`` again.

The hook also books, per iteration, the work the engine did (prompt
panels computed, decode steps with their live lengths), opens and
closes the measured window, and starts and stops the profiler.

Private names of ``repro.serve.engine.Engine`` this module depends on
(pinned by ``test_selftest.py``): ``_sweep_deadlines`` (called first in
every loop iteration), ``_fill_slots``, ``_advance_chunks``, ``queue``
(its ``_Pending`` entries' ``t0``), ``chunking`` (its ``_ChunkState``
entries' ``sched``), ``active``, ``kv_trace``, ``_host_len``.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

from repro.serve.engine import Engine, Request


class Booking:
    """Work done, per loop iteration, stamped with the iteration's
    start on the host clock."""

    def __init__(self):
        self.panels: List[tuple] = []    # (t, tokens, prefix)
        self.steps: List[tuple] = []     # (t, [live lengths])

    def panels_in(self, t0, t1):
        return [(n, p) for t, n, p in self.panels if t0 <= t < t1]

    def steps_in(self, t0, t1):
        return [ls for t, ls in self.steps if t0 <= t < t1]


class BenchEngine(Engine):
    """The engine with a per-iteration hook on its own thread."""

    def __init__(self, *args, hook: Callable[["BenchEngine"], None],
                 **kwargs):
        super().__init__(*args, **kwargs)
        self.hook = hook
        self.booking = Booking()
        self.iter_t = time.perf_counter()
        self.hook_error: Optional[Exception] = None
        self._kv_seen = 0

    # one call per loop iteration, before admission
    def _sweep_deadlines(self):
        prev_t, self.iter_t = self.iter_t, time.perf_counter()
        for ls in self.kv_trace[self._kv_seen:]:
            self.booking.steps.append((prev_t, ls))
        self._kv_seen = len(self.kv_trace)
        if self.hook_error is None:
            try:
                with TraceAnnotation("bench.arrivals"):
                    self.hook(self)
            except Exception as err:       # never into the recovery
                self.hook_error = err      # boundary: stop, re-raise
                self.shutdown()
        super()._sweep_deadlines()

    def _fill_slots(self):
        idle = [s for s in range(self.n_slots)
                if self.active[s] is None and s not in self.chunking]
        with TraceAnnotation("engine.admit"):
            admitted = super()._fill_slots()
        for s in idle:
            if self.active[s] is not None and s not in self.chunking:
                # one-shot prefill of the whole prompt
                self.booking.panels.append(
                    (self.iter_t, int(self._host_len[s]), 0))
        return admitted

    def _advance_chunks(self):
        before = {s: (st.sched[0], len(st.sched))
                  for s, st in self.chunking.items()}
        with TraceAnnotation("engine.chunks"):
            advanced = super()._advance_chunks()
        for s, ((off, clen, _), left) in before.items():
            st = self.chunking.get(s)
            if st is None or len(st.sched) < left:
                self.booking.panels.append((self.iter_t, int(clen),
                                            int(off)))
        return advanced

    def run_from(self):
        """``run`` that keeps the booking of decode steps across calls
        (``run`` starts a fresh ``kv_trace``)."""
        self._kv_seen = 0
        out = self.run(max_steps=10 ** 9)
        for ls in self.kv_trace[self._kv_seen:]:
            self.booking.steps.append((self.iter_t, ls))
        self._kv_seen = len(self.kv_trace)
        if self.hook_error is not None:
            raise self.hook_error
        return out


class Arrivals:
    """The traffic's requests, submitted as they fall due."""

    def __init__(self, requests: List[dict], start: float):
        self.reqs = requests
        self.start = start
        self.next = 0
        self.due = {}        # rid -> due time (host clock)

    def pending(self) -> bool:
        return self.next < len(self.reqs)

    def next_due(self) -> float:
        return self.start + self.reqs[self.next]["due"]

    def inject(self, eng: Engine, now: float):
        while self.pending() and self.next_due() <= now:
            r = self.reqs[self.next]
            due = self.next_due()
            eng.submit(Request(rid=self.next, prompt=r["prompt"],
                               max_new=r["max_new"]))
            # time the request from when it was due, not submitted
            eng.queue[-1].t0 = due
            self.due[self.next] = due
            self.next += 1

