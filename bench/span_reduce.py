"""The program's own host spans (``percepta.*``, ``repro.runtime.spans``) in
a profiler trace, beside what ``trace_reduce`` reads there.

``reduce`` gives, inside ``bench.window``:

- ``spans``: for each span name its count, seconds, self seconds (the time
  no child span on the same thread covers), whether it is a leaf, the sums
  of its numeric metadata, and each event's metadata;
- ``idle_gaps``: the device's idle gaps labelled as ``trace_reduce.reduce``
  labels them, except that idle time under ``bench.run_windows`` goes to
  the innermost program span open on the manager's thread at each instant
  (time under none keeps the ``bench.*`` label);
- ``cover``: of the idle time under ``bench.run_windows``, the share that
  program spans name and the share their leaf spans name; and the share of
  the time inside ``bench.run_windows`` that assemble, dispatch and consume
  cover.

``PER_LAYER`` turns the span table into per-layer values: ms a window in
the backlog cell, ms a call in the open loop.

    python3 bench/span_reduce.py --workload <cell> --seed <n> --seconds <s>

runs the cell as ``bench/run.py --trace 1`` does and prints its result
line with a ``program`` entry: the tables above and the per-layer values.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, NamedTuple, Sequence, Tuple

import trace_reduce as tr

PREFIX = "percepta."
STEPS = ("percepta.assemble", "percepta.dispatch", "percepta.consume")


class Span(NamedTuple):
    name: str
    start: int                  # ns
    end: int                    # ns
    thread: Tuple[int, int]     # (plane, line) of the trace
    meta: dict


def load_spans(path: str) -> List[Span]:
    """Every host event named ``percepta.*`` or ``bench.*``, with its
    thread and, for program spans, its metadata."""
    from jax.profiler import ProfileData
    out = []
    for p, pl in enumerate(ProfileData.from_file(path).planes):
        if tr.is_device(tr.Plane(pl.name)):
            continue
        for li, line in enumerate(pl.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    meta = dict(ev.stats)
                elif ev.name.startswith("bench."):
                    meta = {}
                else:
                    continue
                s = int(ev.start_ns)
                out.append(Span(ev.name, s, s + int(ev.duration_ns),
                                (p, li), meta))
    return out


def innermost(spans: Sequence[Span]) -> Tuple[List[tuple], set]:
    """Pieces ``(start, end, name)`` of one thread's timeline, each
    labelled by the innermost span open there (spans on one thread nest),
    sorted and disjoint; and the names that had a child."""
    pieces, parents, stack = [], set(), []
    t = 0

    def close_until(until):
        # pop every span that ends by ``until``, emitting the time since t
        nonlocal t
        while stack and stack[-1].end <= until:
            top = stack.pop()
            if top.end > t:
                pieces.append((t, top.end, top.name))
            t = max(t, top.end)

    for sp in sorted(spans, key=lambda x: (x.start, -x.end)):
        close_until(sp.start)
        if stack:
            if sp.start > t:
                pieces.append((t, sp.start, stack[-1].name))
            parents.add(stack[-1].name)
        stack.append(sp)
        t = sp.start
    close_until(float("inf"))
    return pieces, parents


def _clip(pieces, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in pieces
            if e > lo and s < hi]


def _overlap(pieces, starts, s, e) -> Dict[str, int]:
    """ns of [s, e] under each piece's name (``starts``: the pieces'
    starts, for bisection)."""
    out = defaultdict(int)
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(pieces) and pieces[i][0] < e:
        ov = min(e, pieces[i][1]) - max(s, pieces[i][0])
        if ov > 0:
            out[pieces[i][2]] += ov
        i += 1
    return out


def labelled_gaps(planes: List[tr.Plane], window_span: str,
                  host_spans: Sequence[str]) -> Tuple[int, int, List[tuple]]:
    """``(lo, hi, gaps)``: the window as ``trace_reduce.reduce`` takes it
    and the idle gaps of its first busy device plane, each ``(label,
    start, end)`` with the label ``trace_reduce.reduce`` gives it."""
    host = [e for p in planes if not tr.is_device(p)
            for events in p.lines.values() for e in events]
    windows = [e for e in host if e[0] == window_span]
    devices = [p for p in planes if tr.is_device(p) and any(p.lines.values())]
    if not devices:
        raise ValueError("the trace holds no device plane with events")
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        evs = [e for p in devices for ls in p.lines.values() for e in ls]
        lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    busy = []
    for p in devices:
        ops = p.lines.get(tr.OPS_LINE) or [e for ls in p.lines.values()
                                           for e in ls]
        busy = tr.union(tr._clip([(s, e) for _, s, e in ops], lo, hi))
        if busy:
            break
    spans = [(n, s, e) for n, s, e in host if n in host_spans]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        cover = defaultdict(int)
        for n, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[n] += ov
        gaps.append((max(cover, key=cover.get) if cover else "idle", s, e))
    return lo, hi, gaps


def reduce(planes: List[tr.Plane], spans: List[Span],
           window_span: str = "bench.window",
           host_spans: Sequence[str] = ("bench.deliver", "bench.run_windows"),
           manager_span: str = "bench.run_windows", top: int = 10) -> dict:
    lo, hi, gaps_in = labelled_gaps(planes, window_span, host_spans)
    program = [x for x in spans if x.name.startswith(PREFIX)]
    threads = defaultdict(list)
    for x in program:
        threads[x.thread].append(x)
    pieces_of, parents, self_ns = {}, set(), defaultdict(int)
    for th, evs in threads.items():
        pieces, par = innermost(evs)
        pieces_of[th] = pieces
        parents |= par
        for s, e, name in _clip(pieces, lo, hi):
            self_ns[name] += e - s
    ns = 1e-9
    table: Dict[str, dict] = {}
    for x in program:
        if x.end <= lo or x.start >= hi:
            continue
        row = table.setdefault(x.name, {"count": 0, "seconds": 0.0,
                                        "sums": {}, "events": []})
        row["count"] += 1
        row["seconds"] += (min(x.end, hi) - max(x.start, lo)) * ns
        for k, v in x.meta.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["sums"][k] = row["sums"].get(k, 0) + v
        row["events"].append(x.meta)
    for name, row in table.items():
        row["self_seconds"] = self_ns[name] * ns
        row["leaf"] = name not in parents

    # the manager's thread: the one that holds most manager spans
    runs = [x for x in spans if x.name == manager_span]
    count = defaultdict(int)
    for x in runs:
        count[x.thread] += 1
    pieces = pieces_of.get(max(count, key=count.get), []) if count else []
    starts = [p[0] for p in pieces]
    gaps = defaultdict(int)
    under = named = leaf = 0
    for label, s, e in gaps_in:
        if label != manager_span:
            gaps[label] += e - s
            continue
        under += e - s
        split = _overlap(pieces, starts, s, e)
        for name, v in split.items():
            gaps[name] += v
            named += v
            leaf += v if name not in parents else 0
        gaps[label] += (e - s) - sum(split.values())
    run_ns = sum(max(min(x.end, hi) - max(x.start, lo), 0) for x in runs)
    steps_s = sum(table[n]["seconds"] for n in STEPS if n in table)
    return {
        "spans": table,
        "idle_gaps": sorted(([k, v * ns] for k, v in gaps.items() if v > 0),
                            key=lambda kv: -kv[1])[:top],
        "cover": {
            "manager_idle_s": under * ns,
            "named_share": named / under if under else None,
            "leaf_share": leaf / under if under else None,
            "steps_share": steps_s / (run_ns * ns) if run_ns else None,
        },
    }


# --- per-layer values from the span table ---------------------------------

def windows(table: dict):
    """Windows run inside the window: the sum of ``k`` over the batches."""
    return table.get("percepta.batch", {}).get("sums", {}).get("k")


def calls(table: dict):
    return table.get("percepta.run_windows", {}).get("count")


def _ms_per(table: dict, name: str, per, key: str = None):
    """ms of span ``name`` (or the sum of its metadata ``key``, already in
    ms) per unit ``per``; None where the trace holds no such span."""
    row, n = table.get(name), per(table)
    if not row or not n:
        return None
    if key is None:
        return 1e3 * row["seconds"] / n
    v = row["sums"].get(key)
    return None if v is None else v / n


def _median_meta(table: dict, name: str, key: str):
    vals = [m[key] for m in table.get(name, {}).get("events", [])
            if key in m]
    return float(statistics.median(vals)) if vals else None


PER_LAYER = {
    "assemble_host_ms.backlog":
        lambda t: _ms_per(t, "percepta.assemble", windows),
    "close_windows_host_ms.backlog":
        lambda t: _ms_per(t, "percepta.assemble", windows, "close_ms"),
    "dispatch_host_ms.backlog":
        lambda t: _ms_per(t, "percepta.dispatch", windows),
    "result_wait_ms.backlog":
        lambda t: _ms_per(t, "percepta.result_wait", windows),
    "forward_host_ms.backlog":
        lambda t: _ms_per(t, "percepta.forward", windows),
    "queue_wait_ms.open":
        lambda t: _median_meta(t, "percepta.assemble", "queue_wait_ms"),
    "assemble_host_ms.open":
        lambda t: _ms_per(t, "percepta.assemble", calls),
    "dispatch_host_ms.open":
        lambda t: _ms_per(t, "percepta.dispatch", calls),
}


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    import run
    import spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, run.ROOT)
    program = {}
    load = tr.load

    def load_both(path):
        # run_cell reduces the trace file and then deletes it: read the
        # program spans from it on the way
        planes = load(path)
        program.update(reduce(planes, load_spans(path)))
        return planes

    tr.load = load_both
    try:
        result = run.run_cell(cell, args.seed, args.seconds, True,
                              log=lambda s: print(s, file=sys.stderr,
                                                  flush=True))
    except run.NoChip as exc:
        print(f"bench/span_reduce.py: {exc}", file=sys.stderr)
        return 3
    finally:
        tr.load = load
    if program:
        table = program["spans"]
        program["per_layer"] = {k: f(table) for k, f in PER_LAYER.items()}
        program["windows"], program["calls"] = windows(table), calls(table)
        for row in table.values():
            row.pop("events")
    result["program"] = program
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
