#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Needs a TPU with as many chips as the cell
asks for; without one it exits with code 2 and prints no result. The last
line of standard output is the result's JSON object; the numbers compared
with the reference, each beside its limit, are the last lines of standard
error. JAX's persistent compilation cache lives in ``.chipbench_cache`` at
the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".chipbench_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from chipbench import harness

    chips = harness.load_cell(harness.load_benchmark(), args.workload)["chips"]
    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if d0.platform != "tpu" or len(devices) < chips:
        print(f"chipbench: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {d0.platform} device(s)", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return harness.main_run(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
