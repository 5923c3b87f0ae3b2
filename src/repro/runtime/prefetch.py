"""WindowPrefetcher — double-buffered host-side window assembly.

The scan engine's Manager loop is a strict alternation: drain queues and
build the (K, E, S, M) batch on the host, THEN dispatch ``run_many`` and
wait. The device idles through every ``close_windows`` pass and the host
idles through every device batch. This module pipelines the two: a pump
thread assembles window batch *j+1* (clock advance -> receiver poll ->
queue drain -> ``FleetAccumulator.close_windows`` -> staged ``RawWindow``)
while batch *j* executes on device via JAX's async dispatch; the Manager
blocks only when it consumes batch *j*'s results.

Bit-identity with the synchronous ``scan`` mode is BY CONSTRUCTION, via a
deterministic batch-epoch handoff protocol:

  * the Manager submits :class:`BatchPlan`s (epoch-numbered, chronologically
    ordered window bounds) on an unbounded task queue;
  * the pump thread is the ONLY pumper/drainer in async modes and processes
    plans strictly in epoch order, performing exactly the clock-advance /
    poll / drain sequence the synchronous loop would have performed at the
    same window boundaries — so every record lands in the same batch;
  * assembled batches travel back on a depth-1 buffer (the "double" in
    double-buffered: one batch on device, at most one staged ahead), which
    also bounds host memory when the device falls behind; this depth is
    what sizes the system's rotating staging-buffer pool (at most four
    batches are ever alive: assembling, staged, taken, and the previous
    one in flight until it is consumed — see
    ``PerceptaSystem._STAGE_DEPTH``);
  * the Manager consumes batches in epoch order and verifies the epoch tag
    on every handoff.

Pump-thread exceptions are captured and re-raised in the Manager thread at
the handoff point, so a failing drain/close surfaces exactly like it would
synchronously.
"""
from __future__ import annotations

import queue
import threading
from typing import List, NamedTuple, Optional, Tuple


class BatchPlan(NamedTuple):
    epoch: int                 # strictly increasing handoff tag
    bounds: List[Tuple[float, float]]
    pump: bool                 # advance the clock + poll receivers first
    membership: int = 0        # env-membership epoch the plan was built under


class AssembledBatch(NamedTuple):
    epoch: int
    bounds: List[Tuple[float, float]]
    raw: object                # RawWindow (K, E, S, M), window-relative ts
    counts: List[int]
    membership: int = 0        # echoed from the plan; Manager verifies it


class _PumpError(NamedTuple):
    epoch: int
    exc: BaseException


_STOP = object()


class WindowPrefetcher:
    """Owns the pump thread; one instance per system, lazily started.

    ``assemble(bounds, pump)`` is the system callback doing the actual
    clock-advance/poll/drain/close work — injecting it keeps this module
    free of system internals and trivially testable.
    """

    def __init__(self, assemble, depth: int = 1):
        assert depth >= 1
        self._assemble = assemble
        self._depth = depth
        self._tasks: "queue.Queue" = queue.Queue()
        self._ready: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stopping = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._next_submit = 0      # next epoch to hand to the pump
        self._next_consume = 0     # next epoch the Manager must receive
        self._failed: Optional[BaseException] = None

    # --- lifecycle -----------------------------------------------------------
    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._pump_loop,
                                            name="window-prefetch",
                                            daemon=True)
            self._thread.start()

    def stop(self):
        """Stop the pump thread; safe to call repeatedly / when never run.

        Works even when the Manager abandoned assembled batches (e.g. a
        consumer exception mid-run): the stop flag unblocks a pump stuck on
        the full ready buffer, and all queues/epoch counters are reset so a
        later submit() starts from a clean handoff state instead of
        replaying stale plans."""
        if self._thread is not None and self._thread.is_alive():
            self._stopping.set()
            self._tasks.put(_STOP)
            self._thread.join(timeout=10.0)
        self._thread = None
        self._stopping = threading.Event()
        self._tasks = queue.Queue()
        self._ready = queue.Queue(maxsize=self._depth)
        self._next_submit = 0
        self._next_consume = 0

    # --- Manager side --------------------------------------------------------
    def submit(self, bounds, pump: bool = True, membership: int = 0) -> int:
        """Queue one batch plan; returns its epoch tag.

        ``membership`` tags the plan with the env-membership epoch it was
        built under; elastic systems verify it on the assembled batch so
        attach/detach can only land at batch boundaries (no plan built
        before the change is ever consumed after it)."""
        if self._failed is not None:
            raise RuntimeError("window prefetcher failed") from self._failed
        self._ensure_thread()
        epoch = self._next_submit
        self._next_submit += 1
        self._tasks.put(BatchPlan(epoch, list(bounds), pump, membership))
        return epoch

    def in_flight(self) -> int:
        """Plans submitted but not yet consumed (0 = a true batch boundary)."""
        return self._next_submit - self._next_consume

    def next_batch(self, timeout: float = 600.0) -> AssembledBatch:
        """Block for the next assembled batch, verifying the epoch handoff.

        Re-raises any exception the pump thread hit while assembling (the
        pump stops at the first failure, so the error epoch is always the
        one the Manager is waiting on)."""
        got = self._ready.get(timeout=timeout)
        if isinstance(got, _PumpError):
            self._failed = got.exc
            raise got.exc
        assert got.epoch == self._next_consume, \
            f"epoch handoff violated: got {got.epoch}, " \
            f"expected {self._next_consume}"
        self._next_consume += 1
        return got

    # --- pump side -----------------------------------------------------------
    def _put_ready(self, item) -> bool:
        """Blocking put that stays responsive to stop(): a Manager that
        abandons its batches must not wedge the pump on the full buffer."""
        while not self._stopping.is_set():
            try:
                self._ready.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _pump_loop(self):
        while not self._stopping.is_set():
            task = self._tasks.get()
            if task is _STOP:
                return
            try:
                raw, counts = self._assemble(task.bounds, task.pump)
            except BaseException as e:  # propagate to the Manager thread
                self._put_ready(_PumpError(task.epoch, e))
                return
            if not self._put_ready(AssembledBatch(task.epoch, task.bounds,
                                                  raw, counts,
                                                  task.membership)):
                return
