"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 chipbench/calibrate.py --workload <cell> --seconds 2 --seeds 11 12 13 ...

In one process and on the chip: builds the cell's program once, then for
each seed makes the seeded data, drives a short window at the cell's own
load through the same loop as a benchmark run, and prints one JSON line
with the program's compared numbers (``program``) and those of the
control, the reference one precision step below the configuration's in
the program's place, and for a training cell of each fault planted in the
reference. The last line sums up: for each number the largest program
reading (the lower reading of its limit) and, per variant, the smallest
(its upper reading). The benchmark's own runs never run this.
"""
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def calibrate(bench, cell: str, seeds, seconds: float, say=print) -> dict:
    run = harness.Run(bench, cell)
    run.operand = run.make_operand()
    loop = bench.loop(run.traffic)(run)
    lower: dict = {}
    upper: dict = {}
    for seed in seeds:
        loop.load(seed)
        host = loop.window(seconds, lambda _name: contextlib.nullcontext())
        prog = loop.readings()
        variants = loop.control_readings()
        say(json.dumps({"seed": seed, "units": host["attempted"], "failed": host["failed"],
                        "program": prog, **variants}))
        for k, v in prog.items():
            lower[k] = max(lower.get(k, 0.0), v)
        for name, readings in variants.items():
            for k, v in readings.items():
                upper.setdefault(name, {})[k] = min(upper.get(name, {}).get(k, float("inf")), v)
    return {"cell": cell, "seeds": list(seeds), "lower": lower, "upper": upper}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = harness.Bench(ROOT)
    devs = harness.require_chips(int(bench.cell(args.workload)["chips"]))
    harness.enable_compile_cache(bench)
    print(f"device: {devs[0].device_kind}, {len(devs)} devices", flush=True)
    summary = calibrate(bench, args.workload, args.seeds, args.seconds,
                        say=lambda s: print(s, flush=True))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
