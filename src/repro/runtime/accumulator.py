"""Accumulator — provider-agnostic per-environment collection.

"Each environment has its own dedicated Accumulator instance, which listens
to the corresponding queue. Upon receiving a message, it forwards the data
to the environment-specific Manager." Here the Accumulator also performs the
device-batch assembly: records -> padded (streams, max_samples) arrays with
validity masks for the windows that just closed.

Semantics, shared by both classes below: window k takes the not-yet-taken
records with ts < t_end_k in timestamp order (arrival order breaking ties),
overflow beyond ``max_samples`` drops the OLDEST and is counted, records
older than t_start_k still occupy slots but are masked invalid, and records
newer than the last window end stay pending.

:class:`Accumulator` is one environment's reference implementation: a list
of pending column chunks closed by one global stable lexsort. It is what
``ingest_fastpath=False`` runs, and its ``stats`` dict is each environment's
record, unknown-stream and overflow accounting on either path.

:class:`FleetAccumulator` makes the same decisions for every environment
of a system at once: ``assemble_windows`` gathers all drained queues into
one set of columns keyed by ``slot * S + stream`` and closes them into the
(K, E, S, M) staging buffers with a handful of whole-fleet NumPy calls,
instead of one ``Accumulator`` round per environment, stream and batch.
Window k's records of one key are the same records in the same order —
ordered by (timestamp, arrival) — so the staging bytes, overflow drops and
stats are identical.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from repro.runtime.records import RecordBatch

# one pending chunk = (stream_idx int32, ts float64, value float64) columns
Chunk = Tuple[np.ndarray, np.ndarray, np.ndarray]

_TABLE_CACHE_MAX = 256    # stream-index tables cached per accumulator


class Accumulator:
    def __init__(self, env_id: str, streams: Sequence[str], max_samples: int):
        self.env_id = env_id
        self.streams = list(streams)
        self.stream_index = {s: i for i, s in enumerate(self.streams)}
        self.max_samples = max_samples
        # stream-name tuple -> stream-index table (ingest_batch does not
        # rebuild the mapping per call; batches reuse interned tuples)
        self._table_cache: dict = {}
        self._chunks: List[Chunk] = []
        self.stats = {"records": 0, "unknown_stream": 0, "overflow": 0}

    # --- ingest ---------------------------------------------------------------
    def ingest(self, items: Sequence):
        """Accept a drained queue mix of ``Record``s and ``RecordBatch``es."""
        sid, ts, vs = [], [], []
        for it in items:
            if isinstance(it, RecordBatch):
                # flush interleaved singles first to preserve arrival order
                if sid:
                    self._push_columns(np.asarray(sid, np.int32),
                                       np.asarray(ts, np.float64),
                                       np.asarray(vs, np.float64))
                    sid, ts, vs = [], [], []
                self.ingest_batch(it)
                continue
            idx = self.stream_index.get(it.stream)
            if idx is None:
                self.stats["unknown_stream"] += 1
                continue
            sid.append(idx)
            ts.append(it.timestamp)
            vs.append(it.value)
        if sid:
            self._push_columns(np.asarray(sid, np.int32),
                               np.asarray(ts, np.float64),
                               np.asarray(vs, np.float64))

    def ingest_batch(self, batch: RecordBatch):
        """Columnar ingest: resolve the batch's stream table, drop unknowns."""
        n = len(batch)
        streams = batch.streams
        table = self._table_cache.get(streams)
        if table is None:
            if len(self._table_cache) >= _TABLE_CACHE_MAX:
                self._table_cache.clear()
            table = np.asarray([self.stream_index.get(s, -1)
                                for s in streams], np.int32)
            self._table_cache[streams] = table
        sid = table[batch.stream_ids] if n else np.empty(0, np.int32)
        # float64 columns regardless of how the batch was built, so window
        # bucketing always compares like Record's Python floats
        ts = np.asarray(batch.timestamps, np.float64)
        vs = np.asarray(batch.values, np.float64)
        known = sid >= 0
        n_unknown = int((~known).sum())
        if n_unknown:
            self.stats["unknown_stream"] += n_unknown
            sid, ts, vs = sid[known], ts[known], vs[known]
        self._push_columns(sid, ts, vs)

    def _push_columns(self, sid: np.ndarray, ts: np.ndarray, vs: np.ndarray):
        """Store known-stream columns (arrival order)."""
        n = int(sid.shape[0])
        if n:
            self.stats["records"] += n
            self._chunks.append((sid, ts, vs))

    def reset(self) -> int:
        """Discard pending records (elastic detach); returns the count."""
        n = self.pending()
        self._chunks = []
        return n

    def pending(self) -> int:
        """Records held for windows after the last close."""
        return sum(int(c[0].shape[0]) for c in self._chunks)

    def _pending(self) -> Chunk:
        if not self._chunks:
            z = np.empty(0)
            return np.empty(0, np.int32), z, z
        if len(self._chunks) > 1:
            self._chunks = [tuple(np.concatenate(cols)
                                  for cols in zip(*self._chunks))]
        return self._chunks[0]

    # --- window close ---------------------------------------------------------
    def close_window(self, t_start: float, t_end: float, rebase: bool = False):
        """Build the padded raw-window arrays for [t_start, t_end) and retain
        newer records for later windows."""
        v, ts, m = self.close_windows([(t_start, t_end)], rebase=rebase)
        return v[0], ts[0], m[0]

    def close_windows(self, bounds, rebase: bool = False, out=None):
        """Close K consecutive windows into stacked (K, S, M) arrays.

        ``bounds`` is a chronologically ordered sequence of (t_start, t_end)
        pairs; records newer than the last window end stay pending. One
        global stable lexsort by (window, stream, ts) orders the taken
        records; overflow is trimmed from the oldest side, then
        values/timestamps/validity scatter in one shot.

        ``out=(values, ts, valid)`` writes into caller-provided PRE-ZEROED
        (K, S, M) arrays (may be strided views into a larger staging
        buffer) instead of allocating. The returned triple is ``out``
        itself.

        ``rebase=True`` emits WINDOW-RELATIVE timestamps: each record's ts
        has its window's ``t_start`` subtracted in float64 *before* the
        float32 cast, so sub-second deltas stay exact on arbitrarily long
        horizons (absolute float32 seconds quantize to >=1s past t~2^24,
        ~194 days of stream time — minutes of wall time at high speedup).
        This is the device-staging form the system modes consume (the
        pipeline receives ``window_start = 0``); all bucketing / ordering /
        validity decisions are made on the float64 absolute columns either
        way, so ``rebase`` changes only the emitted frame.
        """
        K, S, M = len(bounds), len(self.streams), self.max_samples
        if out is not None:
            values, ts_out, valid = out
        else:
            values = np.zeros((K, S, M), np.float32)
            ts_out = np.zeros((K, S, M), np.float32)
            valid = np.zeros((K, S, M), bool)
        starts = np.asarray([b[0] for b in bounds], np.float64)
        ends = np.asarray([b[1] for b in bounds], np.float64)
        sid, ts, vs = self._pending()
        if not sid.shape[0]:
            return values, ts_out, valid
        # window index: first k with ts < ends[k]; >= K stays pending
        bucket = np.searchsorted(ends, ts, side="right")
        taken = bucket < K
        self._chunks = [] if taken.all() else \
            [(sid[~taken], ts[~taken], vs[~taken])]
        sid, ts, vs, bucket = sid[taken], ts[taken], vs[taken], bucket[taken]
        if not sid.shape[0]:
            return values, ts_out, valid
        # stable sort by (window, stream, ts) — ties keep arrival order,
        # matching the reference's stable per-stream list sort
        group = bucket.astype(np.int64) * S + sid
        order = np.lexsort((ts, group))
        group = group[order]
        sid, ts, vs, bucket = sid[order], ts[order], vs[order], bucket[order]

        cnt = np.bincount(group, minlength=K * S)
        first = cnt.cumsum() - cnt                     # group start offsets
        pos = np.arange(group.shape[0]) - first[group]
        drop = np.maximum(cnt - M, 0)                  # overflow: drop oldest
        self.stats["overflow"] += int(drop.sum())
        keep = pos >= drop[group]
        slot = (pos - drop[group])[keep]
        kb, sb, tk, vk = bucket[keep], sid[keep], ts[keep], vs[keep]
        values[kb, sb, slot] = vk.astype(np.float32)
        tk_out = tk - starts[kb] if rebase else tk       # float64 subtract
        ts_out[kb, sb, slot] = tk_out.astype(np.float32)
        valid[kb, sb, slot] = tk >= starts[kb]
        return values, ts_out, valid


class FleetAccumulator:
    """Every environment's pending records in one columnar store.

    Rows are ``(key, ts, value)`` with ``key = slot * S + stream``, kept in
    arrival order: per environment the drain order, within a batch its row
    order. The carried rows are never re-sorted: each close orders every
    window's rows of a key by (ts, arrival) itself. A key belongs to one slot whatever the pool's width, so growing
    an elastic pool leaves the store as it is; :meth:`discard` empties one
    slot (detach).

    :meth:`ingest` takes one batch's drained queues and returns its
    per-window record counts; :meth:`close_windows` closes K consecutive
    windows of every key into caller-provided staging buffers and keeps the
    records newer than the last window end pending. ``sort_fallback`` (1
    when the last close had to order records by timestamp) and
    :meth:`pending` are the pass's counters; ``merge_stats`` totals the
    closes by how they were ordered.
    """

    def __init__(self, streams: Sequence[str], max_samples: int):
        self.streams = list(streams)
        self.stream_index = {s: i for i, s in enumerate(self.streams)}
        self.max_samples = max_samples
        self._table_cache: dict = {}
        # (key int64, ts float64, value float64) chunks, arrival order
        self._chunks: List[Chunk] = []
        self.sort_fallback = 0
        # close_fast: already grouped; close_sort: one stable sort by
        # group; close_lexsort: ordered by timestamp within each group
        self.merge_stats = {"close_fast": 0, "close_sort": 0,
                            "close_lexsort": 0}

    def pending(self) -> int:
        """Records held for windows after the last close."""
        return sum(int(c[0].shape[0]) for c in self._chunks)

    def discard(self, slot: int) -> int:
        """Drop one slot's pending records (elastic detach); returns the
        count, as ``Accumulator.reset`` does."""
        S = len(self.streams)
        n, rest = 0, []
        for key, ts, vs in self._chunks:
            keep = (key < slot * S) | (key >= (slot + 1) * S)
            n += int(keep.shape[0] - np.count_nonzero(keep))
            if keep.any():
                rest.append((key[keep], ts[keep], vs[keep]))
        self._chunks = rest
        return n

    # --- ingest ---------------------------------------------------------------
    def ingest(self, drained, starts: np.ndarray, stats) -> np.ndarray:
        """Gather drained queues into the store.

        ``drained`` is ``[(slot, items), ...]``: a slot and the ``Record`` /
        ``RecordBatch`` mix its queue returned. ``stats`` maps a slot to
        its ``Accumulator.stats`` dict; ``records`` and ``unknown_stream``
        are counted into it as ``Accumulator.ingest`` counts them. Returns
        the records per window of window starts ``starts``: every drained
        record counts in the window that holds its timestamp, clipped to
        the batch, unknown streams included."""
        S, K = len(self.streams), starts.shape[0]
        get = self.stream_index.get
        # one key per column part: slot * S + stream, -1 - slot unknown
        keys: list = []
        tss: list = []
        vss: list = []
        for slot, items in drained:
            base, unknown = slot * S, -1 - slot
            singles: list = []
            for it in items:
                if type(it) is not RecordBatch:
                    singles.append(it)
                    continue
                if singles:
                    self._split(base, unknown, singles, keys, tss, vss)
                    singles = []
                streams = it.streams
                if len(streams) == 1:
                    idx = get(streams[0])
                    keys.append(unknown if idx is None else base + idx)
                    tss.append(it.timestamps)
                    vss.append(it.values)
                else:
                    self._split(base, unknown, it, keys, tss, vss)
            if singles:
                self._split(base, unknown, singles, keys, tss, vss)
        if not tss:
            return np.zeros(K, np.int64)
        lens = np.fromiter(map(len, tss), np.int64, len(tss))
        part = np.asarray(keys, np.int64)
        known = part >= 0
        for name, slots, m in (("records", part // S, known),
                               ("unknown_stream", -1 - part, ~known)):
            if m.any():
                n = np.bincount(slots[m], weights=lens[m])
                for s in np.flatnonzero(n):
                    stats[int(s)][name] += int(n[s])
        # float64 columns, so bucketing compares like Record's Python floats
        ts = np.concatenate(tss, dtype=np.float64)
        j = np.searchsorted(starts, ts, side="right") - 1
        counts = np.bincount(np.clip(j, 0, K - 1), minlength=K)
        if not known.any():
            return counts
        if known.all():
            key = np.repeat(part, lens)
            vs = np.concatenate(vss, dtype=np.float64)
        else:
            rows = np.repeat(known, lens)
            key = np.repeat(part[known], lens[known])
            ts = ts[rows]
            vs = np.concatenate([v for v, k in zip(vss, known) if k],
                                dtype=np.float64)
        if key.shape[0]:
            self._chunks.append((key, ts, vs))
        return counts

    def _split(self, base: int, unknown: int, src, keys, tss, vss):
        """Append a multi-stream batch, or a run of ``Record``s, as one
        column part per stream (row order kept within each)."""
        if isinstance(src, RecordBatch):
            streams = src.streams
            table = self._table_cache.get(streams)
            if table is None:
                if len(self._table_cache) >= _TABLE_CACHE_MAX:
                    self._table_cache.clear()
                table = np.asarray([self.stream_index.get(s, -1)
                                    for s in streams], np.int64)
                self._table_cache[streams] = table
            sid = table[src.stream_ids] if len(src) else \
                np.empty(0, np.int64)
            ts, vs = src.timestamps, src.values
        else:
            get = self.stream_index.get
            sid = np.asarray([get(r.stream, -1) for r in src], np.int64)
            ts = np.asarray([r.timestamp for r in src], np.float64)
            vs = np.asarray([r.value for r in src], np.float64)
        for s in np.unique(sid):
            m = sid == s
            keys.append(base + int(s) if s >= 0 else unknown)
            tss.append(ts[m])
            vss.append(vs[m])

    # --- window close ---------------------------------------------------------
    def close_windows(self, bounds, out, stats) -> None:
        """Close K consecutive windows of every key into ``out``.

        ``out=(values, ts, valid)`` are PRE-ZEROED C-contiguous (K, E, S, M)
        arrays; ``stats`` maps a slot to its ``Accumulator.stats`` dict,
        where overflow is counted. Window k takes the pending records with
        ts < t_end_k not taken by an earlier window, ordered by (ts,
        arrival); beyond ``max_samples`` the oldest are dropped and
        counted; records older than t_start_k occupy slots but are masked
        invalid; timestamps are window-relative (the float64 subtract
        before the float32 cast, as ``Accumulator.close_windows(rebase=
        True)``). Records newer than the last window end stay pending.

        A group is one staging row, ``(window, slot, stream)``. When each
        group's rows already lie in one run (a batch per environment and
        stream, as receivers and deliverers publish them) no sort runs;
        otherwise one stable argsort of the group brings them together.
        Only where timestamps then run backwards inside a group are the
        rows ordered by ``(group, ts)`` instead (``sort_fallback``); the
        store keeps arrival order, so ties always fall to arrival."""
        values, ts_out, valid = out
        assert all(a.flags.c_contiguous for a in out)
        K, E, S, M = values.shape
        starts = np.asarray([b[0] for b in bounds], np.float64)
        ends = np.asarray([b[1] for b in bounds], np.float64)
        self.sort_fallback = 0
        # split each chunk into its taken rows and the rest, copying only
        # a chunk that holds both (in a backlog the carried chunk is taken
        # whole and the new one is carried whole)
        parts, rest = [], []
        for key, ts, vs in self._chunks:
            bucket = np.searchsorted(ends, ts, side="right")
            taken = bucket < K
            if taken.all():
                parts.append((key, ts, vs, bucket))
            elif not taken.any():
                rest.append((key, ts, vs))
            else:
                i = np.flatnonzero(taken)
                parts.append((key[i], ts[i], vs[i], bucket[i]))
                i = np.flatnonzero(~taken)
                rest.append((key[i], ts[i], vs[i]))
        self._chunks = rest
        if not parts:
            return
        key, ts, vs, bucket = parts[0] if len(parts) == 1 else \
            (np.concatenate(cols) for cols in zip(*parts))
        n = key.shape[0]
        # the group (window, slot, stream) is the staging row's flat index
        group = bucket * (E * S) + key
        sizes = np.bincount(group, minlength=K * E * S)
        head = np.empty(n, bool)
        head[0] = True
        np.not_equal(group[1:], group[:-1], out=head[1:])
        order = None
        if np.count_nonzero(head) != np.count_nonzero(sizes):
            # a group's rows lie in more than one run: bring them together
            order = np.argsort(group, kind="stable")   # ties: arrival order
            g, t = group[order], ts[order]
            np.not_equal(g[1:], g[:-1], out=head[1:])
        else:
            t = ts
        if (~head[1:] & (t[1:] < t[:-1])).any():
            order = np.lexsort((ts, group))
            g = group[order]
            np.not_equal(g[1:], g[:-1], out=head[1:])
            self.sort_fallback = 1
            self.merge_stats["close_lexsort"] += 1
        else:
            self.merge_stats["close_sort" if order is not None
                             else "close_fast"] += 1
        if order is not None:
            ts, vs = ts[order], vs[order]
            bucket, group = bucket[order], group[order]
        # rank of each row in its group: row index less its run's first
        pos = np.arange(n)
        first = np.where(head, pos, 0)
        np.maximum.accumulate(first, out=first)
        pos -= first
        flat = group * M + pos
        if (pos >= M).any():
            # overflow: drop the oldest of each group beyond max_samples
            over = np.maximum(sizes - M, 0)
            g = np.flatnonzero(over)
            lost = np.bincount(g // S % E, weights=over[g], minlength=E)
            for s in np.flatnonzero(lost):
                stats[int(s)]["overflow"] += int(lost[s])
            drop = over[group]
            i = np.flatnonzero(pos >= drop)
            flat = flat[i] - drop[i]
            bucket, ts, vs = bucket[i], ts[i], vs[i]
        t0 = starts[bucket]
        values.reshape(-1)[flat] = vs.astype(np.float32)
        # float64 subtract, then the float32 cast
        ts_out.reshape(-1)[flat] = (ts - t0).astype(np.float32)
        valid.reshape(-1)[flat] = ts >= t0
