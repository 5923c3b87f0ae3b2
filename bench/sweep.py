"""The knee of an open-loop cell: the highest window rate the system
sustains without a growing backlog, found once by a sweep on the chip.

    for r in 3 4 5; do python3 bench/sweep.py --workload <open cell> --seed <n> --rate $r --windows 240; done

One process per rate: builds the cell's system, warms up its shapes, then
offers ``--rate`` windows per second for ``--windows`` windows (open loop,
no drain) and prints one JSON line: the windows due, those processed by
the close, the backlog left at the close, the mean windows per dispatch,
and the event-to-action latency of each quarter of the segment's windows.
A rate is sustained where the backlog at the close is under one batch
(``max_k`` windows) and the latency does not climb from quarter to
quarter, as it does behind a growing backlog. The knee is the highest rate
sustained; the cell offers a fixed share of it, written into its traffic
file as a number.
"""
import argparse
import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402  (puts src on the path)
import drive  # noqa: E402
import generator  # noqa: E402
import spec  # noqa: E402
import sut  # noqa: E402


def quarters(lat: np.ndarray) -> list:
    """p50 and p95 latency (ms) of each quarter of the windows due."""
    out = []
    for part in np.array_split(lat, 4):
        done = part[np.isfinite(part)]
        out.append([float(np.percentile(done, q)) if done.size else None
                    for q in (50, 95)])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--windows", type=int, default=240)
    args = ap.parse_args(argv)
    import jax
    cell = spec.load_cell(args.workload, run.ROOT)
    dev = run.devices_for(cell, True)[0]
    run.enable_cache()
    cfg, traffic = cell.config, dict(cell.traffic)
    E = int(cfg["n_envs"])
    ps, ts, _, _ = generator.seed_words(args.seed)
    pool = generator.ReadingPool(cfg, args.seed)
    sink = sut.ActionSink(int(cfg["n_actions"]))
    seconds = args.windows / args.rate
    with jax.default_device(dev):
        system = sut.build(cfg, ps, ts, sink)
        drv = drive.Load(system, generator.Deliverer(system, pool),
                           dict(traffic, windows_per_s=args.rate,
                                drain_s=0.0))
        run.warm_up(drv, traffic)
        rec = drv.open(seconds)
    by_close = sum(b[2] for b in rec.batches if b[1] <= rec.t_end)
    delivered = sum(1 for d in rec.deliveries if d[3] <= rec.t_end)
    lat = run.latencies_ms(rec, sink, E, args.rate)
    lag = [d[2] - d[1] for d in rec.deliveries]
    print(json.dumps({
        "rate": args.rate, "seconds": seconds, "due": rec.windows_due,
        "processed_by_close": int(by_close),
        "backlog_at_close": int(delivered - by_close),
        "sustained_windows_per_s": by_close / seconds,
        "windows_per_dispatch": float(np.mean([b[2] for b in rec.batches])),
        "generator_lag_p99_ms": float(np.percentile(lag, 99)) * 1e3,
        "latency_quarters_p50_p95_ms": quarters(lat)}), flush=True)
    system.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
