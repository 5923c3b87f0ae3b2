"""From a profiler trace (``.xplane.pb``) to the device's busy time, the
device time of each program and op, and the idle gaps, each labelled by
what the host was doing (the harness's ``jax.profiler.TraceAnnotation``
spans).

``load`` turns the file into plain events; ``reduce`` does the
arithmetic, so it can be checked on events written by hand.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]] = field(default_factory=dict)


def load(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for pl in data.planes:
        plane = Plane(pl.name)
        for line in pl.lines:
            plane.lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                for ev in line.events)
        planes.append(plane)
    return planes


def is_device(plane: Plane) -> bool:
    return plane.name.startswith("/device:") and "CPU" not in plane.name


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def program_name(name: str) -> str:
    """``jit_run_many_decide(12)`` -> ``jit_run_many_decide``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def reduce(planes: List[Plane], window_span: str = "bench.window",
           host_spans: Sequence[str] = ("bench.deliver", "bench.run_windows"),
           top: int = 10) -> dict:
    """Busy and idle time of the device planes inside the host span
    ``window_span``.

    Busy is the union of op intervals (the ``XLA Ops`` line, else every
    line of the plane), averaged over the device planes that ran anything.
    Program time sums the ``XLA Modules`` events by name. Each idle gap of
    the first device plane is labelled by the host span in ``host_spans``
    that covers most of it (``idle`` where none does)."""
    host = [e for p in planes if not is_device(p)
            for events in p.lines.values() for e in events]
    windows = [e for e in host if e[0] == window_span]
    devices = [p for p in planes if is_device(p)
               and any(p.lines.values())]
    if not devices:
        raise ValueError("the trace holds no device plane with events")
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        evs = [e for p in devices for ls in p.lines.values() for e in ls]
        lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    busy_each, programs, ops = [], defaultdict(lambda: [0, 0]), defaultdict(int)
    first_busy = None
    for p in devices:
        op_events = p.lines.get(OPS_LINE) or [e for ls in p.lines.values()
                                              for e in ls]
        busy = union(_clip([(s, e) for _, s, e in op_events], lo, hi))
        if not busy:
            continue
        if first_busy is None:
            first_busy = busy
        busy_each.append(sum(e - s for s, e in busy))
        for name, s, e in p.lines.get(MODULES_LINE, []):
            for cs, ce in _clip([(s, e)], lo, hi):
                programs[program_name(name)][0] += ce - cs
                programs[program_name(name)][1] += 1
        for name, s, e in op_events:
            for cs, ce in _clip([(s, e)], lo, hi):
                ops[name] += ce - cs
    busy_ns = sum(busy_each) / len(busy_each) if busy_each else 0.0
    gaps = defaultdict(int)
    spans = [(n, s, e) for n, s, e in host if n in host_spans]
    edges = [lo] + [x for iv in (first_busy or []) for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        cover = defaultdict(int)
        for n, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[n] += ov
        gaps[max(cover, key=cover.get) if cover else "idle"] += e - s
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_ns * ns,
        "n_devices": len(busy_each),
        "programs": {k: {"seconds": v[0] * ns, "count": v[1]}
                     for k, v in programs.items()},
        "device_ops": sorted(([k, v * ns] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v * ns] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }


# The fused window -> decide -> bank program is jitted from a
# ``functools.partial`` and reaches the trace as ``jit__unknown``; a name of
# its own (``run_many_decide``) is matched too, should it get one.
FUSED_PROGRAM = ("run_many_decide", "jit__unknown")
TRAIN_PROGRAM = ("train_step",)


def program_seconds(trace: dict, fragments) -> Tuple[float, int]:
    """Device seconds and executions of the programs whose name holds any
    of ``fragments``."""
    if isinstance(fragments, str):
        fragments = (fragments,)
    secs, count = 0.0, 0
    for name, v in trace["programs"].items():
        if any(f in name for f in fragments):
            secs += v["seconds"]
            count += v["count"]
    return secs, count
