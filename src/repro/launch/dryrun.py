import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first init, and the production meshes below need 512 placeholder
# host devices (16x16 single pod, 2x16x16 multi-pod).  Never set this
# globally — smoke tests and benches must keep seeing 1 CPU device.

"""Multi-pod dry-run CLI: lower + compile every (arch × shape × mesh) cell.

For each cell the appropriate step function (train / prefill / decode) is
``jax.jit(...).lower(*abstract_args).compile()``-d against the production
mesh with explicit in/out shardings.  The compiled artifact yields:

* ``memory_analysis()``  — per-device bytes (proves the cell fits),
* ``cost_analysis()``    — HLO FLOPs / bytes for the roofline terms,
* collective bytes       — parsed from the partitioned HLO text
  (all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute output sizes),

written to ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` for
EXPERIMENTS.md §Dry-run and benchmarks/roofline.py.  All analysis logic
lives in :mod:`repro.launch.analysis` (importable without the 512-device
environment).

Usage::

    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--force]
"""
import argparse
import time

from repro.compile_cache import enable_compile_cache
from repro.configs import SHAPES, list_archs
from repro.launch.analysis import ART_DIR, run_cell


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--moe-impl", choices=("einsum", "scatter"), default="einsum")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default=os.path.normpath(ART_DIR))
    args = ap.parse_args()

    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    cells = []
    if args.all:
        for a in list_archs():
            for s in SHAPES:
                for m in meshes:
                    cells.append((a, s, m))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape, m) for m in meshes]

    failures = 0
    for (a, s, m) in cells:
        t0 = time.monotonic()
        try:
            rec = run_cell(a, s, m, args.out, force=args.force,
                           moe_impl=args.moe_impl,
                           microbatches=args.microbatches)
        except Exception as e:
            failures += 1
            print(f"[FAIL] {a} {s} {m}: {e}", flush=True)
            continue
        dt = time.monotonic() - t0
        if rec.get("skipped"):
            print(f"[skip] {a:24s} {s:12s} {m:6s} — {rec['skipped']}", flush=True)
        else:
            r = rec["roofline"]
            print(f"[ ok ] {a:24s} {s:12s} {m:6s} "
                  f"compute={r['compute_s']*1e3:8.2f}ms "
                  f"memory={r['memory_s']*1e3:8.2f}ms "
                  f"coll={r['collective_s']*1e3:8.2f}ms "
                  f"dom={rec['dominant'][:-2]:10s} "
                  f"hbm={rec['hbm_per_dev_bytes']/2**30:6.2f}GiB "
                  f"({dt:.0f}s)", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
