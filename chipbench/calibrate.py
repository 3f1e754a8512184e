"""Several runs of one cell in one process, for the measurements that set
the benchmark's rates and limits: a sweep of offered rates to find the
knee, and the compared numbers of the program and of its lower-precision
controls over many seeds.  The benchmark's own runs never do this.

    python -m chipbench.calibrate --workload <name> --seeds 1,2,3 \\
        --seconds 20 [--rates 1.0,1.5] [--controls int8,fp8] \\
        [--trace 0] [--out <file.jsonl>]

Needs a TPU like ``chipbench.run``.  Prints one JSON object per run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench.run import CHECKOUT, use_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT / "src"))
    from chipbench import harness
    import jax

    if jax.devices()[0].platform != "tpu":
        print("chipbench.calibrate: needs a TPU", file=sys.stderr)
        return 3
    use_compile_cache(jax)
    bench = harness.load_benchmark()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates \
        else [None]
    controls = tuple(c for c in args.controls.split(",") if c)
    t = T_START
    for rate in rates:
        for seed in seeds:
            res, extra, in_win = harness.run(
                bench, args.workload, seed, args.seconds, bool(args.trace),
                t, controls=controls, rate_per_s=rate)
            row = {"workload": args.workload, "seed": seed, "rate": rate,
                   "seconds": args.seconds, "in_window": in_win,
                   **extra, **res}
            line = json.dumps(row)
            print("CALIBRATE " + line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
            t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
