"""Readings for the limits of ``correct``, for one cell, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 ... --control 3
    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 --control 0 --plant default_precision

Runs the cell once per seed at the cell's own load and window, as
``run.py`` does, and prints each number compared: the program against the
reference on every seed (the lower readings), and the bfloat16 control
against the reference on the first ``--control`` seeds (the upper
readings). One JSON line per seed, then a summary line with the largest
program reading and the smallest control reading of each number.

``--plant default_precision`` runs the program with its float32
contractions at the backend's default matmul precision, which on a TPU
rounds each operand to bfloat16: the program's readings are then those of
that fault.
"""
import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import check  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--plant", choices=("default_precision",))
    args = ap.parse_args(argv)
    if args.plant == "default_precision":
        import jax.numpy as jnp
        from repro.core import f32
        f32.einsum = lambda subscripts, *operands: jnp.einsum(subscripts,
                                                              *operands)
    lower = {k: 0.0 for k in check.NUMBERS}
    upper = {k: float("inf") for k in check.NUMBERS}
    for i, seed in enumerate(args.seeds):
        cell = spec.load_cell(args.workload, run.ROOT)
        t = time.perf_counter()
        res = run.run_cell(cell, seed, args.seconds, trace=False,
                           t_start=t, control=i < args.control,
                           log=lambda s: print(s, file=sys.stderr,
                                               flush=True))
        prog = {k: v["value"] for k, v in res["check"].items()}
        for k in check.NUMBERS:
            lower[k] = max(lower[k], prog[k])
            if "control" in res:
                upper[k] = min(upper[k], res["control"][k])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "program": prog, "control": res.get("control"),
                          "metrics": res["metrics"]}), flush=True)
        del res
        gc.collect()
    print(json.dumps({"workload": args.workload, "plant": args.plant,
                      "seeds": len(args.seeds),
                      "lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
