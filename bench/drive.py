"""The load: a generator thread that delivers windows and the manager
loop that calls ``run_windows``, with host spans around both.

``backlog``: every window is already due. The generator keeps
``lead_windows`` windows delivered ahead of the manager, which runs
``run_windows(k)`` back to back; the window ends at the first batch
completion at or after ``seconds``.

``open``: window ``i`` of the measured window closes at ``T0 + (i + 1) /
windows_per_s`` on the wall clock; the generator delivers it then, and the
manager batches whatever has closed, up to ``max_k`` windows. Windows due
after ``seconds`` are not generated; those generated are waited for up to
``drain_s`` past the close.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class Record:
    """What a run did, on the host clock (``time.perf_counter`` seconds)."""
    load: str
    n_envs: int
    t0: float = 0.0                 # start of the measured window
    t_end: float = 0.0              # end of the measured window
    first_window: int = 0           # first window index of the window
    # per run_windows call: (start, end, k, first window index)
    batches: List[tuple] = field(default_factory=list)
    # per delivered window: (window, due, start, end)
    deliveries: List[tuple] = field(default_factory=list)
    windows_due: int = 0            # open: windows due inside the window


def _span(trace: bool, name: str):
    if trace:
        import jax
        return jax.profiler.TraceAnnotation(name)
    return contextlib.nullcontext()


class Load:
    def __init__(self, system, deliverer, traffic: dict, trace: bool = False):
        self.system = system
        self.deliverer = deliverer
        self.traffic = traffic
        self.trace = trace
        self.delivered = 0          # windows delivered so far
        self.next = 0               # next window the manager takes
        self.cond = threading.Condition()
        self.error: Optional[BaseException] = None
        self.rows: List[dict] = []  # run_windows' result rows, window order
        self.ks: List[int] = []     # k of every run_windows call, in order

    def _run(self, k: int):
        with _span(self.trace, "bench.run_windows"):
            self.rows.extend(self.system.run_windows(k, pump=False))
        self.ks.append(k)

    # --- synchronous steps (set-up) -----------------------------------------
    def deliver_through(self, w_end: int):
        while self.delivered < w_end:
            self.deliverer.deliver(self.delivered)
            self.delivered += 1

    def run_batch(self, k: int):
        """Deliver what the next ``k`` windows need, then run them."""
        self.deliver_through(self.next + k)
        self.next += k
        self._run(k)

    # --- the measured window --------------------------------------------------
    def _thread(self, body):
        def wrapped():
            try:
                body()
            except BaseException as exc:       # re-raised by the manager
                self.error = exc
                with self.cond:
                    self.cond.notify_all()
        th = threading.Thread(target=wrapped, name="bench-generator",
                              daemon=True)
        th.start()
        return th

    def backlog(self, seconds: float) -> Record:
        lead, k = int(self.traffic["lead_windows"]), int(self.traffic["k"])
        rec = Record("backlog", len(self.system.env_ids))
        self.deliver_through(self.next + lead)
        stop = threading.Event()

        def generate():
            while not stop.is_set():
                with self.cond:
                    while self.delivered >= self.next + lead \
                            and not stop.is_set():
                        self.cond.wait(0.1)
                    if stop.is_set():
                        return
                    w = self.delivered
                t = time.perf_counter()
                with _span(self.trace, "bench.deliver"):
                    self.deliverer.deliver(w)
                rec.deliveries.append((w, t, t, time.perf_counter()))
                with self.cond:
                    self.delivered = w + 1
                    self.cond.notify_all()

        rec.first_window = self.next
        rec.t0 = time.perf_counter()
        th = self._thread(generate)
        try:
            with _span(self.trace, "bench.window"):
                while True:
                    with self.cond:
                        while self.delivered < self.next + k \
                                and self.error is None:
                            self.cond.wait(0.1)
                        if self.error is not None:
                            raise self.error
                        w = self.next
                        self.next += k
                        self.cond.notify_all()
                    t = time.perf_counter()
                    self._run(k)
                    end = time.perf_counter()
                    rec.batches.append((t, end, k, w))
                    if end >= rec.t0 + seconds:
                        break
        finally:
            stop.set()
            with self.cond:
                self.cond.notify_all()
            th.join(30)
        rec.t_end = rec.batches[-1][1]
        if self.error is not None:
            raise self.error
        return rec

    def open(self, seconds: float) -> Record:
        rate = float(self.traffic["windows_per_s"])
        max_k = int(self.traffic["max_k"])
        drain = float(self.traffic["drain_s"])
        rec = Record("open", len(self.system.env_ids))
        w0 = self.next
        assert self.delivered == w0, "open loop starts with nothing pending"
        n_due = int(np.floor(seconds * rate + 1e-9))
        rec.windows_due, rec.first_window = n_due, w0
        done, stop = threading.Event(), threading.Event()

        def generate():
            for i in range(n_due):
                due = rec.t0 + (i + 1) / rate
                while not stop.is_set():
                    now = time.perf_counter()
                    if now >= due:
                        break
                    time.sleep(min(due - now, 0.05))
                if stop.is_set():
                    break
                t = time.perf_counter()
                with _span(self.trace, "bench.deliver"):
                    self.deliverer.deliver(w0 + i)
                rec.deliveries.append((w0 + i, due, t, time.perf_counter()))
                with self.cond:
                    self.delivered = w0 + i + 1
                    self.cond.notify_all()
            done.set()
            with self.cond:
                self.cond.notify_all()

        rec.t0 = time.perf_counter()
        rec.t_end = rec.t0 + seconds
        th = self._thread(generate)
        try:
            with _span(self.trace, "bench.window"):
                while True:
                    with self.cond:
                        while self.delivered <= self.next \
                                and not done.is_set() and self.error is None:
                            self.cond.wait(0.05)
                        if self.error is not None:
                            raise self.error
                        avail = self.delivered - self.next
                        if avail == 0 and done.is_set():
                            break
                        if time.perf_counter() > rec.t_end + drain:
                            break
                        k = min(max_k, avail)
                        w = self.next
                        self.next += k
                    t = time.perf_counter()
                    self._run(k)
                    rec.batches.append((t, time.perf_counter(), k, w))
        finally:
            stop.set()
            th.join(30)
        if self.error is not None:
            raise self.error
        return rec
