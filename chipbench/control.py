#!/usr/bin/env python3
"""The readings a cell's limits are set from.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

In one process, for each seed: one run of the cell as the benchmark makes
it (its own window and comparison), whose ``max_rel_err`` and
``rms_rel_err`` are the program's readings; then the controls, the
reference at ``high`` precision (three bfloat16 passes, written out) and at
``high_chip`` (the chip's own ``Precision.HIGH``) in the program's place
over the same pool, read against the reference at ``highest``. One JSON
line per seed on standard output. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_readings(cfg: dict, pool_size: int, seed: int, device) -> dict:
    """Each control's numbers over the pool of ``seed``: the reference at
    ``high`` and at ``high_chip`` in the program's place, read against the
    reference at ``highest``."""
    from chipbench import reference

    params = reference.make_params(cfg, seed, device=device)
    pool = reference.input_pool(cfg, pool_size, seed)
    ref = reference.outputs(cfg, params, pool, "highest", device=device)
    return {p: reference.compare(reference.outputs(cfg, params, pool, p, device=device), ref)
            for p in ("high", "high_chip")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from chipbench import harness, reference, run

    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = harness.load_benchmark()
    cell = harness.load_cell(bench, args.workload)
    cfg = reference.load_config(cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(bench, cell, cfg, traffic, seed, args.seconds,
                               False, devices, time.perf_counter())
        program = {k: res["checks"][k]["value"] for k in ("max_rel_err", "rms_rel_err")}
        print(json.dumps({
            "workload": args.workload, "seed": seed, "correct": res["correct"],
            "completed": res["attempted"] - res["failed"], "program": program,
            **control_readings(cfg, traffic["pool"], seed, devices[0]),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
