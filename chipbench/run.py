"""Run one cell of the chip benchmark once.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Cells, configurations and metrics are named in ``BENCHMARK.json`` at the
root of the checkout; everything else is found by those names under
``chipbench/``.  Runs on the machine it is started on and needs a TPU
with as many chips as the cell asks for: with anything else it exits 3
and prints no result.  JAX's persistent compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` at the root
of the checkout.  Earlier lines report set-up by phase, the window's
calls and counters, and the comparison with the plain reference; the
compared numbers and their limits are the last lines on standard error;
the last line on standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``), and ``checks`` last.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def use_compile_cache(jax) -> str:
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(CHECKOUT))
    from chipbench import harness

    bench = harness.load_benchmark()
    spec = harness.cell_spec(bench, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(spec["chips"]):
        print(f"chipbench: the cell needs {spec['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    cache = use_compile_cache(jax)
    print(f"device: {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)
    result, _, _ = harness.run(bench, args.workload, args.seed,
                               args.seconds, bool(args.trace), T_START)
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
