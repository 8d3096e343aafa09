"""Operator-granularity scheduling demo: slice -> schedule -> execute.

Lowers a layer-DAG model into per-tile slice tasks through the **nested
tiling IR** (conv/pool channel, row, or 2-D (cout × rows) grid tiles; dense
row blocks; attention head blocks) with direct slice-to-slice edges,
schedules the sliced DAG with the fast-path heuristics, optionally tightens
the result with a warm-started branch-and-bound budget, and executes the
sliced plan — verifying it is numerically identical to the unsliced
sequential reference.  Prints the scheduled comm volume of the direct
lowering next to the ``tile_concat`` lowering so the halo-aware-edge win is
visible.

Factor selection (the canonical per-layer mapping interface):

* default            — ``uniform_factors(model, --factor[, --spatial])``;
* ``--auto-factors`` — :func:`choose_slice_factors`: roofline-parity search
                       over 1-D counts and (cout × rows) grid candidates;
* ``--grid``         — :func:`search_slice_factors`: schedule-aware
                       coordinate descent over grid candidates, then a
                       report of the chosen per-layer tile grids and the
                       makespan/comm-bytes win over the best uniform
                       single-axis tiling.

The TPU-priced paper-size run reproduces the 2-D acceptance number
(>= 10% below the best 1-D tiling on 8 workers):

    PYTHONPATH=src python examples/schedule_sliced.py \
        --model inception --input 224 --hw tpu --grid

``--segmented`` additionally compiles the sliced plan through the MPMD
executor (packed registers, per-segment kernel tables, ring comm rounds),
verifies it against the sequential reference, and reports its trace
(lowering) time.

``--profile`` builds the executor and prints each segment's
static statistics (ticks, signatures, ring rounds, comm patterns, span
and window-gather coverage, window elements and their gather indices,
landing scans, columns landed per request and their efficiency) beside the whole call's warm best-of-3 wall time; with ``--grid`` it
first prints the slice search's counters (schedules, memo hits, layers,
sliced layers, tasks), as its ``/repro/plan/slice_search`` event reports
them.  The device time of each phase (assembly, kernels, comm, ...) is
read from a profiler trace of the executor, whose ops carry named scopes
(``codegen/executor.py``).  The full GoogLeNet's m=1 plan as the chip
benchmark builds it:

    PYTHONPATH=src python examples/schedule_sliced.py \
        --model googlenet --input 224 --hw tpu --grid --workers 1 --profile

``--stream`` sweeps the executor's ``buffer_depth`` option
(1 = write-once staging, 2/4 = rotating double/quad-buffered staging
frames + donated carry) and prints, per depth, the carry width, resident
staging footprint, retire-copy volume and the warm call time.

``--analyze`` runs the static concurrency analyzer (``codegen/analyze.py``)
on the chosen plan: the happens-before hazard verdict at buffer depths
1/2/4, per-segment access statistics, and the sync-cost/slack report
(zero-slack vs deferrable comm rounds, unread payloads, and either
quantified removable-sync findings or the asserted minimality verdict).

    PYTHONPATH=src python examples/schedule_sliced.py \
        [--model inception|googlenet|lenet5|transformer] [--input 64]
        [--workers 8]
        [--factor 8] [--spatial] [--auto-factors | --grid] [--hw keystone|tpu]
        [--tighten-s 0] [--segmented] [--profile] [--stream] [--analyze]
"""
import argparse
import os
import time

# the --segmented demo meshes over placeholder host devices; the flag must
# be set before jax initializes
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.codegen import build_mpmd_executor, build_plan, interpret_plan, plan_summary
from repro.core import dsh, ish, speedup, tighten_schedule, validate
from repro.core.costmodel import KEYSTONE_CPU, TPU_V5E
from repro.models.cnn import (
    googlenet,
    inception_net,
    lenet5,
    run_sequential,
    transformer_block,
)
from repro.models.slicing import (
    choose_slice_factors,
    search_slice_factors,
    slice_model,
    slicing_summary,
    uniform_factors,
)


def fmt_factor(f):
    if isinstance(f, tuple):
        return f"{f[0]}c x {f[1]}r grid"
    return f"{f} tiles"


def grid_report(model, hw, time_unit, workers, factors):
    """--grid satellite: chosen per-layer grids + makespan/bytes vs the
    best uniform single-axis tiling."""
    print("chosen per-layer tile grids:")
    for name, f in sorted(factors.items()):
        print(f"  {name:24s} {fmt_factor(f)}")

    def schedule(fs):
        sliced = slice_model(model, fs)
        sdag = sliced.to_dag(hw, time_unit=time_unit)
        best = None
        for heur in (ish, dsh):
            s = heur(sdag, workers)
            mk = s.makespan(sdag)
            if best is None or mk < best[0]:
                plan = build_plan(s, sdag)
                bytes_ = plan.comm_bytes(
                    {l.name: l.out_bytes() for l in sliced.layers}
                )
                best = (mk, bytes_)
        return best

    best_1d = None
    for n in (4, 8):
        for spatial in (False, True):
            mk, b = schedule(uniform_factors(model, n, spatial=spatial))
            tag = f"{'rows' if spatial else 'chan'} x{n}"
            print(f"  1-D {tag:9s}: makespan {mk:10.1f}  comm {b / 1e6:7.2f} MB")
            if best_1d is None or mk < best_1d[0]:
                best_1d = (mk, b, tag)
    g_mk, g_b = schedule(factors)
    print(f"  2-D grid     : makespan {g_mk:10.1f}  comm {g_b / 1e6:7.2f} MB")
    print(f"grid vs best 1-D ({best_1d[2]}): makespan {g_mk / best_1d[0]:.3f}x, "
          f"comm bytes {g_b / max(best_1d[1], 1):.3f}x")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model",
                    choices=("inception", "googlenet", "lenet5", "transformer"),
                    default="inception")
    ap.add_argument("--input", type=int, default=64,
                    help="input resolution of the CNN models")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--factor", type=int, default=8,
                    help="uniform per-layer tile count (uniform_factors)")
    ap.add_argument("--spatial", action="store_true",
                    help="uniform conv/pool tiles along output rows instead "
                         "of channels")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--auto-factors", action="store_true",
                      help="per-layer factors from the roofline parity search "
                           "over 1-D and grid candidates (choose_slice_factors;"
                           " --factor caps the tile budget)")
    mode.add_argument("--grid", action="store_true",
                      help="schedule-aware grid search (search_slice_factors) "
                           "+ per-layer grid report vs the best 1-D tiling")
    ap.add_argument("--hw", choices=("keystone", "tpu"), default="keystone",
                    help="cost model pricing the DAG (keystone: the paper's "
                         "compute-dominated regime; tpu: bytes/latency-bound)")
    ap.add_argument("--tighten-s", type=float, default=0.0,
                    help="warm-started branch-and-bound budget (0 = off)")
    ap.add_argument("--skip-exec", action="store_true",
                    help="skip the numerical-equivalence execution check")
    ap.add_argument("--segmented", action="store_true",
                    help="compile the sliced plan through the MPMD "
                         "executor, verify it against the sequential "
                         "reference, and report its trace time")
    ap.add_argument("--profile", action="store_true",
                    help="per-segment static span/round statistics of the "
                         "executor and its warm best-of-3 call "
                         "time; with --grid, the slice search's counters")
    ap.add_argument("--stream", action="store_true",
                    help="buffer_depth sweep {1,2,4} of the "
                         "executor: per-depth carry width, staging "
                         "footprint, retire volume and warm call time")
    ap.add_argument("--analyze", action="store_true",
                    help="static concurrency analysis of the chosen plan "
                         "(codegen/analyze.py): happens-before hazard "
                         "verdict at buffer depths 1/2/4, per-segment "
                         "access statistics, and the sync-cost/slack "
                         "report (removable-sync findings or the asserted "
                         "minimality verdict)")
    args = ap.parse_args()
    if args.spatial and (args.grid or args.auto_factors):
        ap.error("--spatial only applies to uniform factors; the grid/parity "
                 "searches pick each layer's axes themselves")

    model = {
        "inception": lambda: inception_net(args.input),
        "googlenet": lambda: googlenet(args.input),
        "lenet5": lambda: lenet5(28),
        "transformer": lambda: transformer_block(64, 128, 8, 256),
    }[args.model]()
    hw = KEYSTONE_CPU if args.hw == "keystone" else TPU_V5E
    time_unit = 1e-6 if args.hw == "keystone" else 1e-9

    if args.grid:
        search = {}

        def listen(event, seconds, **attrs):
            if event == "/repro/plan/slice_search":
                search.update(seconds=round(seconds, 3), **attrs)

        jax.monitoring.register_event_duration_secs_listener(listen)
        factors = search_slice_factors(model, hw, m=args.workers,
                                       time_unit=time_unit)
        jax.monitoring.unregister_event_duration_listener(listen)
        if args.profile:
            print(f"slice search: {search}")
        grid_report(model, hw, time_unit, args.workers, factors)
    elif args.auto_factors:
        factors = choose_slice_factors(model, hw,
                                       max_factor=max(args.factor, 2))
        print(f"auto factors: {factors}")
    else:
        factors = uniform_factors(model, args.factor, spatial=args.spatial)
    sliced = slice_model(model, factors)
    print(f"== {model.name}: {slicing_summary(model, sliced)} ==")

    dag = model.to_dag(hw, time_unit=time_unit)
    sdag = sliced.to_dag(hw, time_unit=time_unit)
    print(f"layer DAG: {len(dag.nodes)} tasks, max parallelism "
          f"{dag.max_parallelism()};  sliced DAG: {len(sdag.nodes)} tasks, "
          f"max parallelism {sdag.max_parallelism()}")

    best = None
    ish_slice = None
    for name, fn in (("ISH", ish), ("DSH", dsh)):
        s_layer = fn(dag, args.workers)
        s_slice = fn(sdag, args.workers)
        validate(s_slice, sdag)
        if name == "ISH":
            ish_slice = s_slice
        mk_l, mk_s = s_layer.makespan(dag), s_slice.makespan(sdag)
        print(f"{name}-{args.workers}: layer makespan {mk_l:9.1f} "
              f"(speedup {speedup(s_layer, dag):4.2f})  |  sliced "
              f"{mk_s:9.1f} (speedup {speedup(s_slice, sdag):4.2f}, "
              f"{mk_l / mk_s:4.2f}x vs layer)")
        if best is None or mk_s < best[1]:
            best = (s_slice, mk_s)

    # comm volume before/after direct slice-to-slice edges, same schedule
    # heuristic: the tile_concat lowering reassembles every sliced layer, so
    # consumers ship whole layer outputs; direct edges ship tile windows
    concat_sliced = slice_model(model, factors, direct=False)
    cdag = concat_sliced.to_dag(hw, time_unit=time_unit)
    c_plan = build_plan(ish(cdag, args.workers), cdag)
    d_plan = build_plan(ish_slice, sdag)
    c_b = c_plan.comm_bytes({l.name: l.out_bytes() for l in concat_sliced.layers})
    d_b = d_plan.comm_bytes({l.name: l.out_bytes() for l in sliced.layers})
    print(f"scheduled comm volume (ISH-{args.workers}): tile_concat "
          f"{c_b / 1e6:.2f} MB -> direct edges {d_b / 1e6:.2f} MB "
          f"(concat/direct {c_b / max(d_b, 1):.2f}x)")

    sched = best[0]
    if args.tighten_s > 0:
        r = tighten_schedule(sdag, args.workers, sched, timeout_s=args.tighten_s)
        print(f"warm-started B&B ({args.tighten_s}s budget): "
              f"{best[1]:9.1f} -> {r.makespan:9.1f} "
              f"({'optimal' if r.optimal else 'anytime'})")
        sched = r.schedule

    plan = build_plan(sched, sdag)
    ps = plan_summary(plan, sdag)
    print(f"plan: {ps['supersteps']} supersteps, {ps['transfers']} transfers "
          f"across {ps['origins']} originating layers "
          f"(max {ps['max_transfers_per_origin']} transfers per layer)")

    if args.analyze:
        analyze_report(plan, sdag, sliced)

    if not args.skip_exec or args.segmented or args.profile or args.stream:
        key = jax.random.PRNGKey(0)
        params = model.init_params(key)
        x = jax.random.normal(key, (2, *model.layers[0].out_shape))
        ref = run_sequential(model, params, x)
    if not args.skip_exec:
        y = interpret_plan(plan, sliced, params, x)
        print(f"max|sliced parallel - sequential| = "
              f"{float(jnp.abs(y - ref).max()):.2e}")

    if args.segmented or args.profile or args.stream:
        if jax.device_count() < args.workers:
            print(f"--segmented/--profile/--stream: skipped "
                  f"({jax.device_count()} devices < {args.workers} workers; "
                  f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                  f"{args.workers})")
            return
        mesh = jax.make_mesh((args.workers,), ("workers",))
    if args.segmented:
        f = build_mpmd_executor(plan, sliced, params, mesh, batch=2)
        t0 = time.perf_counter()
        f.lower(x)
        trace_ms = (time.perf_counter() - t0) * 1e3
        err = float(jnp.abs(f(x) - ref).max())
        print(f"MPMD executor: trace {trace_ms:7.1f} ms, "
              f"max|y - sequential| = {err:.2e}")

    if args.profile:
        profile_segments(plan, sliced, params, mesh, x, ref)

    if args.stream:
        stream_report(plan, sliced, params, mesh, x, ref)


def analyze_report(plan, sdag, sliced):
    """--analyze satellite: static hazard + sync-cost report.

    Runs the happens-before analyzer (superstep-level HB graph over every
    compute/transfer, then the cell-level staging simulation at streaming
    buffer depths 1/2/4) and prints the hazard verdict, the per-segment
    access statistics of the coalesced segmented lowering, and the sync
    report — zero-slack vs deferrable comm rounds, unread payloads, and
    either quantified removable-sync findings or the asserted minimality
    verdict."""
    from repro.codegen import coalesce_transfer_steps
    from repro.codegen.analyze import analyze_plan

    t0 = time.perf_counter()
    rep = analyze_plan(coalesce_transfer_steps(plan), sdag, sliced,
                       depths=(1, 2, 4))
    dt = (time.perf_counter() - t0) * 1e3
    print(f"== static concurrency analysis ({dt:.0f} ms) ==")
    for line in rep.summary(max_hazards=12).splitlines():
        print(f"  {line}")
    if rep.segments:
        print(f"  {'seg':>4} {'steps':>9} {'ticks':>5} {'rounds':>6} "
              f"{'retired':>8} {'hazards':>7}")
        for row in rep.segments:
            lo, hi = row["steps"]
            print(f"  {row['segment']:>4} {f'{lo}-{hi}':>9} "
                  f"{row['ticks']:>5} {row['rounds']:>6} "
                  f"{row['retired_elems']:>8} {row['hazards']:>7}")
    s = rep.sync
    if s:
        print(f"  slack: {s['zero_slack_transfers']}/{s['consumed_transfers']}"
              f" consumed payloads needed on the next superstep; "
              f"{s['deferrable_rounds']}/{s['comm_rounds']} rounds "
              f"deferrable; {s['unread_transfers']} unread transfers "
              f"({s['unread_elems']} elems)")


def _best_ms(fn, *a, n=3):
    """Warm best-of-``n`` wall time of ``fn(*a)`` (the first call compiles)."""
    jax.block_until_ready(fn(*a))
    b = None
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        dt = time.perf_counter() - t0
        b = dt if b is None else min(b, dt)
    return b * 1e3


def profile_segments(plan, sliced, params, mesh, x, ref):
    """--profile satellite: per-segment static statistics.

    Prints each segment's ticks, signatures, ring rounds, comm patterns,
    span and window-gather coverage, window elements and window indices,
    landing scans, columns landed per request and their efficiency, and
    the whole executor's warm best-of-3 call time.
    Device time per phase comes from a profiler trace of the call: the
    executor's ops carry ``seg<k>/<phase>`` named scopes."""
    batch = x.shape[0]
    f = build_mpmd_executor(plan, sliced, params, mesh, batch=batch)
    err = float(jnp.abs(f(x) - ref).max())
    print(f"MPMD executor: max|y - sequential| = {err:.2e}, "
          f"warm call {_best_ms(f, x):.2f} ms")
    print(f"{'seg':>4} {'steps':>9} {'ticks':>5} {'sigs':>4} {'rnds':>4} "
          f"{'pats':>4} {'cov':>5} {'win':>5} {'win_elems':>10} "
          f"{'win_idx':>8} {'land':>4} {'land_elems':>11} {'l_eff':>5}")
    for k, st in enumerate(f.segment_stats):
        lo, hi = st["steps"]
        print(f"{k:>4} {f'{lo}-{hi}':>9} {st['ticks']:>5} {st['sigs']:>4} "
              f"{st['rounds']:>4} {st['comm_patterns']:>4} "
              f"{st['span_coverage']:>5.2f} {st['window_coverage']:>5.2f} "
              f"{st['window_elems']:>10} {st['window_indices']:>8} "
              f"{st['land_runs']:>4} {st['land_elems']:>11} "
              f"{st['land_efficiency']:>5.3f}")


def stream_report(plan, sliced, params, mesh, x, ref):
    """--stream satellite: buffer-depth sweep.

    Builds the executor at ``buffer_depth`` 1, 2 and 4 and
    prints each depth's carry width, resident per-worker staging
    footprint (counted once, not per fire), retire-copy volume (columns
    moved home before a rotating frame is reused) and warm call time.
    Outputs are bit-identical across depths, so the sweep is purely a
    cost trade: depth >= 2 shrinks the carry (frames rotate instead of
    accumulating) at the price of the retire copies."""
    batch = x.shape[0]
    print(f"{'depth':>5} {'width':>9} {'staging':>10} {'retire':>8} | "
          f"{'call':>8}  (ms)")
    for depth in (1, 2, 4):
        f = build_mpmd_executor(plan, sliced, params, mesh, batch=batch,
                                buffer_depth=depth)
        err = float(jnp.abs(f(x) - ref).max())
        assert err < 1e-4, f"depth {depth} diverged: {err:.2e}"
        st0 = f.segment_stats[0]
        staging = st0["peak_staging_elems"] * 4 * batch
        retire = sum(st["retire_elems"] for st in f.segment_stats)
        print(f"{depth:>5} {f.width:>9} {staging / 1e6:>8.2f}MB {retire:>8} | "
              f"{_best_ms(f, x):>8.2f}")

if __name__ == "__main__":
    main()
