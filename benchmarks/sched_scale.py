"""Scheduler + executor scaling benchmark on the CPU.

Times the fast-path pipeline across DAG sizes and worker counts:

* ``ish`` / ``dsh``     — heap-driven :func:`repro.core.list_schedule`
* ``plan``              — cursor-based :func:`repro.codegen.build_plan`
* ``sliced``            — operator-granularity scheduling: lenet5/inception
                          lowered by :func:`repro.models.slicing.slice_model`
                          (uniform per-layer factor mappings) with **direct
                          slice-to-slice edges** vs both the
                          layer-granularity DAGs and the ``tile_concat``
                          lowering (makespan strictly below the concat
                          slicer, and — the halo-aware spatial rows —
                          scheduled transfer bytes reduced >= 2x, asserted
                          on 8 workers)
* ``grid``              — 2-D (cout × rows) tiling: the schedule-aware
                          :func:`repro.models.slicing.search_slice_factors`
                          grid mapping on TPU-priced paper-size inception
                          (224) must schedule at most 0.9x the best uniform
                          single-axis tiling on 8 workers (the nested
                          tiling IR acceptance gate)
* ``analysis``          — static hazard analysis: the happens-before
                          analyzer (``codegen/analyze.py``) proves the
                          headline grid-sliced inception(64) m=8 plan
                          hazard-free at streaming depth 2 (every run; the
                          trend-gated ``analyze_s`` row) and across the
                          1/2/4 depth sweep (full runs)
* ``fault``             — recovery-cost rows: the deterministic
                          kill → detect → replan → migrate → resume drill
                          (``runtime/faults.py``) on sliced lenet5 (always —
                          the CI fault smoke) and grid-sliced inception(64)
                          m=8 (full runs); resumed output asserted allclose
                          to ``run_sequential``, replan wall time and
                          migrated bytes join the trend gates
* ``serve_chaos``       — zero-loss chaos serving drill
                          (``benchmarks/serve_chaos.py``): seeded Poisson
                          trace with deadlines/backpressure through the
                          sliced-plan ``serve.Frontend`` while a campaign
                          kills one worker and straggles another mid-trace;
                          asserts zero request loss, full recovery (dead +
                          cordoned workers out of the final fleet) and
                          seed-identical replay; p50/p99/shed/requests-per-s
                          reported, ``replan_s`` and ``migrated_bytes`` join
                          the trend gates (sliced lenet5 m=4 always — the CI
                          smoke; 1k-request grid-sliced inception(64) m=8 on
                          full runs)
* ``stream gate``       — the ``buffer_depth`` sweep on the grid-sliced
                          inception(64) m=8 plan
                          (``benchmarks/stream_overlap.py``): per-depth
                          sustained supersteps/s through the serving
                          frontend and the resident staging footprint;
                          depth >= 2 must sustain
                          ``STREAM_SPEEDUP`` (1.2x) over depth 1 or beat
                          the ``STREAM_FLOOR_STEPS_S`` absolute floor (the
                          1-core CI escape), and
                          ``peak_staging_bytes`` is deterministic so the
                          ``kind="stream"`` rows join the byte trend gate
* reference equivalence — on sizes where the original O(V²·E) driver is
                          affordable, asserts the fast path produces
                          **identical** schedules (same instances, same
                          makespan)

Writes ``BENCH_sched.json`` next to the repo root and hard-fails if
ISH on the 1000-node / density-0.10 / 8-worker random DAG exceeds the
10 s acceptance budget, if any equivalence check diverges, or — the trend
gate — if any scheduler row regresses more than 2x *and* more than 250 ms
against the committed baseline (``--baseline``; the absolute slack keeps
millisecond rows and cross-machine variance from flaking the gate while a
complexity blowup on any row still trips it), or if any sliced row's total
scheduled transfer bytes grow more than 1.5x over the committed baseline
(bytes are deterministic, so the factor needs no absolute slack).

    PYTHONPATH=src python benchmarks/sched_scale.py [--quick] [--out PATH]
        [--baseline PATH]
"""
import os

# must be set before jax initializes — the stream section meshes over fake
# host devices
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import sys
import time

from repro.core import random_dag, validate
from repro.core.list_scheduling import list_schedule, list_schedule_reference
from repro.codegen import build_plan

ISH_1000_8_BUDGET_S = 10.0  # acceptance bar for the fast path
DSH_ISH_RATIO_BUDGET = 3.0  # regression bar for the shared-cache DSH search
                            # (measured ~2x at 2000 nodes / 8 workers)
TREND_FACTOR = 2.0          # fail if a row gets >2x slower than baseline...
TREND_SLACK_S = 0.25        # ...and slower by this much absolutely (so fast
                            # rows still catch complexity blowups without
                            # millisecond noise or cross-machine 2x flakes)
BYTES_TREND_FACTOR = 1.5    # fail if a sliced row's scheduled transfer bytes
                            # grow >1.5x vs baseline (deterministic, no slack)
DIRECT_BYTES_REDUCTION = 2.0  # acceptance: halo-aware direct edges must at
                              # least halve sliced-inception comm volume vs
                              # the tile_concat slicer (spatial rows, 8 wrk)
GRID_VS_1D_BUDGET = 0.9     # acceptance: the searched 2-D grid tiling must
                            # schedule >= 10% below the best uniform 1-D
                            # tiling on TPU-priced inception(224), 8 workers
                            # (deterministic scheduling -> no slack needed)


def bench_schedulers(sizes, workers, density, ref_max_nodes, results):
    equiv_checked = 0
    for n in sizes:
        dag = random_dag(n, density, seed=0)
        for m in workers:
            for name, dup in (("ish", False), ("dsh", True)):
                t0 = time.perf_counter()
                sched = list_schedule(dag, m, duplicate=dup)
                dt = time.perf_counter() - t0
                validate(sched, dag)
                t0 = time.perf_counter()
                plan = build_plan(sched, dag)
                plan_dt = time.perf_counter() - t0
                row = {
                    "kind": "scheduler",
                    "algo": name,
                    "n_nodes": n,
                    "n_workers": m,
                    "density": density,
                    "schedule_s": round(dt, 4),
                    "plan_s": round(plan_dt, 4),
                    "makespan": sched.makespan(dag),
                    "supersteps": len(plan.steps),
                    "transfers": plan.n_transfers,
                }
                if n <= ref_max_nodes:
                    t0 = time.perf_counter()
                    ref = list_schedule_reference(dag, m, duplicate=dup)
                    row["reference_s"] = round(time.perf_counter() - t0, 4)
                    assert sched.instances == ref.instances, (
                        f"fast path diverged from reference: {name} n={n} m={m}"
                    )
                    row["matches_reference"] = True
                    row["speedup_vs_reference"] = round(
                        row["reference_s"] / max(dt, 1e-9), 2
                    )
                    equiv_checked += 1
                results.append(row)
                print(
                    f"{name:4s} n={n:5d} m={m}  schedule {dt:7.3f}s  "
                    f"plan {plan_dt:6.3f}s  makespan {row['makespan']:9.1f}"
                    + (
                        f"  (= reference, {row['speedup_vs_reference']}x faster)"
                        if "matches_reference" in row
                        else ""
                    )
                )
    return equiv_checked


def bench_sliced(workers, results, slice_factor=8):
    """Operator-granularity scheduling: direct slice-to-slice edges vs both
    the layer-granularity DAG and the ``tile_concat`` lowering."""
    from repro.core import validate as validate_sched
    from repro.core.costmodel import KEYSTONE_CPU
    from repro.models.cnn import inception_net, lenet5
    from repro.models.slicing import slice_model, uniform_factors

    # always include 8 workers: the acceptance gates below must run in the
    # --quick CI smoke too (sliced DAGs are tiny, so this costs milliseconds)
    workers = sorted(set(workers) | {8})
    for model in (lenet5(28), inception_net(64)):
        dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        # layer-granularity reference makespans depend only on (m, algo)
        layer_mks = {
            (m, name): list_schedule(dag, m, duplicate=dup).makespan(dag)
            for m in workers for name, dup in (("ish", False), ("dsh", True))
        }
        for spatial in (False, True):
            factors = uniform_factors(model, slice_factor, spatial=spatial)
            direct = slice_model(model, factors)
            concat = slice_model(model, factors, direct=False)
            sdag = direct.to_dag(KEYSTONE_CPU, time_unit=1e-6)
            cdag = concat.to_dag(KEYSTONE_CPU, time_unit=1e-6)
            d_bytes = {l.name: l.out_bytes() for l in direct.layers}
            c_bytes = {l.name: l.out_bytes() for l in concat.layers}
            for m in workers:
                for name, dup in (("ish", False), ("dsh", True)):
                    layer_mk = layer_mks[(m, name)]
                    t0 = time.perf_counter()
                    sched = list_schedule(sdag, m, duplicate=dup)
                    dt = time.perf_counter() - t0
                    validate_sched(sched, sdag)
                    mk = sched.makespan(sdag)
                    tb = build_plan(sched, sdag).comm_bytes(d_bytes)
                    c_sched = list_schedule(cdag, m, duplicate=dup)
                    c_mk = c_sched.makespan(cdag)
                    c_tb = build_plan(c_sched, cdag).comm_bytes(c_bytes)
                    results.append({
                        "kind": "sliced_scheduler",
                        "model": model.name,
                        "algo": name,
                        "slice_factor": slice_factor,
                        "spatial": spatial,
                        "n_nodes": len(sdag.nodes),
                        "n_workers": m,
                        "schedule_s": round(dt, 4),
                        "makespan": mk,
                        "layer_makespan": layer_mk,
                        "speedup_vs_layer": round(layer_mk / mk, 2),
                        "transfer_bytes": tb,
                        "concat_makespan": c_mk,
                        "concat_transfer_bytes": c_tb,
                        "bytes_reduction_vs_concat": round(tb and c_tb / tb, 2),
                    })
                    print(
                        f"{name:4s} sliced {model.name:9s} x{slice_factor}"
                        f"{'r' if spatial else 'c'} m={m}  "
                        f"schedule {dt:7.3f}s  makespan {mk:9.1f} "
                        f"(layer {layer_mk:9.1f}, {layer_mk / mk:.2f}x; "
                        f"concat {c_mk:9.1f})  bytes {tb / 1e6:6.2f}MB "
                        f"(concat {c_tb / 1e6:6.2f}MB, {c_tb / max(tb, 1):.2f}x)"
                    )
                    if m >= 8:
                        # acceptance: slicing must beat layer granularity
                        # where the layer DAG is narrower than the pool, and
                        # direct edges must beat the tile_concat slicer
                        assert mk < layer_mk, (
                            f"sliced {model.name} m={m} {name}: {mk} !< {layer_mk}"
                        )
                        assert mk < c_mk, (
                            f"direct {model.name} m={m} {name}: {mk} !< "
                            f"concat {c_mk}"
                        )
                        if model.name == "inception" and spatial:
                            # halo-aware rows: >= 2x less scheduled traffic
                            assert tb * DIRECT_BYTES_REDUCTION <= c_tb, (
                                f"direct bytes {tb} not {DIRECT_BYTES_REDUCTION}x "
                                f"under concat {c_tb} ({name} m={m})"
                            )


def bench_grid(results):
    """2-D (cout × rows) grid acceptance: the schedule-aware grid search on
    TPU-priced paper-size inception (224) must schedule at most
    ``GRID_VS_1D_BUDGET`` (0.9x) of the best uniform single-axis tiling on
    8 workers.  Scheduling is deterministic, so the gate needs no slack."""
    from repro.core.costmodel import TPU_V5E
    from repro.models.cnn import inception_net
    from repro.models.slicing import (
        search_slice_factors,
        slice_model,
        uniform_factors,
    )

    m = 8
    model = inception_net(224)

    def best_over_heuristics(factors):
        sliced = slice_model(model, factors)
        sdag = sliced.to_dag(TPU_V5E, time_unit=1e-9)
        best = None
        for name, dup in (("ish", False), ("dsh", True)):
            sched = list_schedule(sdag, m, duplicate=dup)
            validate(sched, sdag)
            mk = sched.makespan(sdag)
            if best is None or mk < best[0]:
                tb = build_plan(sched, sdag).comm_bytes(
                    {l.name: l.out_bytes() for l in sliced.layers}
                )
                best = (mk, name, tb, len(sdag.nodes))
        return best

    best_1d = None
    for n in (4, 8):
        for spatial in (False, True):
            mk, algo, tb, nn = best_over_heuristics(
                uniform_factors(model, n, spatial=spatial)
            )
            tag = f"{'rows' if spatial else 'chan'}{n}"
            print(f"grid-bench 1-D {tag:7s} m={m}: makespan {mk:10.1f} "
                  f"({algo})  bytes {tb / 1e6:6.2f}MB")
            if best_1d is None or mk < best_1d[0]:
                best_1d = (mk, tag)

    t0 = time.perf_counter()
    factors = search_slice_factors(model, TPU_V5E, m=m)
    search_s = time.perf_counter() - t0
    n_grids = sum(
        1 for v in factors.values()
        if isinstance(v, tuple) and v[0] > 1 and v[1] > 1
    )
    mk, algo, tb, nn = best_over_heuristics(factors)
    ratio = mk / best_1d[0]
    results.append({
        "kind": "grid_scheduler",
        "model": model.name,
        "input_hw": 224,
        "hw": "tpu-v5e",
        "n_workers": m,
        "n_nodes": nn,
        "search_s": round(search_s, 2),
        "makespan": mk,
        "algo": algo,
        "transfer_bytes": tb,
        "best_1d_makespan": best_1d[0],
        "best_1d": best_1d[1],
        "grid_layers": n_grids,
        "ratio_vs_best_1d": round(ratio, 4),
    })
    print(f"grid-bench 2-D search m={m}: makespan {mk:10.1f} ({algo}, "
          f"{n_grids} grid layers, search {search_s:.1f}s)  "
          f"ratio vs best 1-D ({best_1d[1]}) = {ratio:.3f}")
    assert n_grids >= 2, f"search found only {n_grids} 2-D grid layers"
    assert ratio <= GRID_VS_1D_BUDGET, (
        f"2-D grid makespan {mk} not {GRID_VS_1D_BUDGET}x under best 1-D "
        f"{best_1d[0]} ({best_1d[1]}): ratio {ratio:.3f}"
    )


def bench_plan_analysis(results, quick):
    """Static hazard analysis on the headline config: the happens-before
    analyzer (``codegen/analyze.py``) must prove the grid-sliced
    inception(64) m=8 plan hazard-free — race-free, donation-safe,
    sync-sufficient, deterministic — at the streaming buffer depths, and
    its wall time joins the trend gates (``analyze_s``) so the cell-level
    simulation can't silently decay into the dominant cost of ``make
    check``.  Quick runs analyze depth 2 (the streaming default the CI run
    gate executes at); full runs sweep 1/2/4."""
    from repro.core import dsh
    from repro.core.costmodel import KEYSTONE_CPU
    from repro.codegen import coalesce_transfer_steps
    from repro.codegen.analyze import analyze_plan
    from repro.models.cnn import inception_net
    from repro.models.slicing import slice_model, uniform_factors

    m = 8
    model = inception_net(64)
    base = uniform_factors(model, 8, spatial=True)
    factors = {k: ((2, 4) if v == (1, 8) else v) for k, v in base.items()}
    sliced = slice_model(model, factors)
    sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
    plan = coalesce_transfer_steps(build_plan(dsh(sdag, m), sdag))

    # depth 2 is always analyzed (and hence always trend-gated — the quick
    # CI row must key-match a baseline row the full run wrote); full runs
    # add the 1/2/4 sweep as a second row
    for depths in ((2,),) if quick else ((2,), (1, 2, 4)):
        t0 = time.perf_counter()
        rep = analyze_plan(plan, sdag, sliced, depths=depths)
        analyze_s = time.perf_counter() - t0
        assert rep.ok, "headline plan has hazards:\n" + rep.summary()
        results.append({
            "kind": "plan_analysis",
            "model": model.name,
            "n_workers": m,
            "depths": list(depths),
            "analyze_s": round(analyze_s, 3),
            "analyze_ms": round(analyze_s * 1e3, 1),
            "cell_accesses": rep.stats.get("cell_events", 0),
            "superstep_events": rep.stats.get("plan_events", 0),
            "sync_verdict": rep.sync.get("verdict", ""),
        })
        print(f"plan-analysis {model.name} m={m} depths={list(depths)}: "
              f"{analyze_s * 1e3:.0f}ms — {rep.summary().splitlines()[0]}")


def bench_fault_recovery(results, quick):
    """Recovery-cost rows: the kill → detect → replan → migrate → resume
    drill on sliced plans (``runtime/faults.py``), with the resumed output
    asserted allclose to ``run_sequential`` — the CI fault smoke gate.

    Quick mode runs the sliced-lenet5 kill campaign only; the full run adds
    the headline grid-sliced inception(64) m=8 drill.  Replan wall time
    joins the timing trend gate (``replan_s``) and migrated bytes are
    deterministic, so they join the byte trend gate like transfer bytes.
    """
    import jax
    import numpy as np
    from repro.core.costmodel import KEYSTONE_CPU
    from repro.models.cnn import inception_net, lenet5, run_sequential
    from repro.models.slicing import slice_model, uniform_factors
    from repro.runtime import kill_and_resume_drill

    key = jax.random.PRNGKey(0)
    cases = [("lenet5", lenet5(28), uniform_factors(lenet5(28), 4), 4, 2, 1)]
    if not quick:
        model = inception_net(64)
        base = uniform_factors(model, 8, spatial=True)
        grid = {k: ((2, 4) if v == (1, 8) else v) for k, v in base.items()}
        cases.append(("inception@grid2x4", model, grid, 8, 4, 3))
    for tag, model, factors, m, kill_step, kill_worker in cases:
        params = model.init_params(key)
        x = jax.numpy.zeros((1, *model.layers[0].out_shape)) + jax.random.normal(
            key, (1, *model.layers[0].out_shape)
        )
        ref = run_sequential(model, params, x)
        sliced = slice_model(model, factors)
        sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        t0 = time.perf_counter()
        res = kill_and_resume_drill(
            sliced, params, x, sdag, m=m, kill_step=kill_step,
            kill_worker=kill_worker, hw=KEYSTONE_CPU,
        )
        drill_s = time.perf_counter() - t0
        ok = bool(np.allclose(np.asarray(res["output"]), np.asarray(ref),
                              atol=1e-4))
        assert ok, f"fault drill {tag} m={m}: resumed output diverged"
        assert res["detected"], f"fault drill {tag}: death not detected"
        assert res["recomputed_supersteps"] <= 1, (
            f"fault drill {tag}: resumed past the interrupted superstep"
        )
        results.append({
            "kind": "fault_recovery",
            "model": tag,
            "n_workers": m,
            "n_nodes": len(sdag.nodes),
            "kill_step": res["kill_step"],
            "kill_worker": res["kill_worker"],
            "supersteps_old": res["n_steps_old"],
            "supersteps_new": res["n_steps_new"],
            "replan_s": round(res["replan_ms"] / 1e3, 4),
            "migrated_bytes": res["migrated_bytes"],
            "placements": res["placements"],
            "completed_nodes": res["completed_nodes"],
            "recomputed_nodes": res["recomputed_nodes"],
            "recomputed_supersteps": res["recomputed_supersteps"],
            "allclose": ok,
            "drill_s": round(drill_s, 2),
        })
        print(
            f"fault {tag:18s} m={m} kill@{res['kill_step']}/w{res['kill_worker']}: "
            f"replan {res['replan_ms']:6.1f}ms  migrated "
            f"{res['migrated_bytes'] / 1e3:7.1f}KB ({res['placements']} "
            f"placements)  recomputed {res['recomputed_nodes']} nodes / "
            f"{res['recomputed_supersteps']} superstep  allclose={int(ok)}"
        )


def check_trend(results, baseline_path):
    """Fail on >TREND_FACTOR slowdowns vs the committed baseline rows."""

    def key(r):
        if r.get("kind") == "scheduler":
            return ("scheduler", r["algo"], r["n_nodes"], r["n_workers"],
                    r.get("density"))
        if r.get("kind") == "sliced_scheduler":
            return ("sliced", r["model"], r["algo"], r["slice_factor"],
                    r.get("spatial", False), r["n_workers"])
        if r.get("kind") == "grid_scheduler":
            return ("grid", r["model"], r["input_hw"], r["n_workers"])
        if r.get("kind") == "fault_recovery":
            return ("fault", r["model"], r["n_workers"], r["kill_step"])
        if r.get("kind") == "serve_chaos":
            return ("serve", r["model"], r["n_workers"], r["n_requests"])
        if r.get("kind") == "stream":
            return ("stream", r["model"], r["n_workers"], r["buffer_depth"])
        if r.get("kind") == "plan_analysis":
            return ("analysis", r["model"], r["n_workers"],
                    tuple(r["depths"]))
        return None

    if not os.path.exists(baseline_path):
        print(f"trend: no baseline at {baseline_path}; skipping")
        return 0
    with open(baseline_path) as f:
        base_rows = json.load(f).get("results", [])
    base = {key(r): r for r in base_rows if key(r)}
    checked = 0
    failures = []
    for r in results:
        b = base.get(key(r))
        if b is None:
            continue
        for field in ("schedule_s", "plan_s", "replan_s", "analyze_s"):
            bv, cv = b.get(field), r.get(field)
            if bv is None or cv is None:
                continue
            checked += 1
            if cv > max(TREND_FACTOR * bv, bv + TREND_SLACK_S):
                failures.append(
                    f"{key(r)} {field}: {cv}s vs baseline {bv}s "
                    f"(> {TREND_FACTOR}x and > +{TREND_SLACK_S}s)"
                )
        # byte-volume gates: scheduled transfer bytes, migrated recovery
        # bytes, and the streaming executor's resident staging footprint
        # are deterministic, so any >1.5x growth is a real regression
        # (a zero-byte baseline row fails on any growth at all)
        for field in ("transfer_bytes", "migrated_bytes",
                      "peak_staging_bytes"):
            bv, cv = b.get(field), r.get(field)
            if bv is None or cv is None:
                continue
            checked += 1
            if cv > BYTES_TREND_FACTOR * bv:
                failures.append(
                    f"{key(r)} {field}: {cv} vs baseline {bv} "
                    f"(> {BYTES_TREND_FACTOR}x)"
                )
    if failures:
        raise AssertionError("perf trend regression:\n" + "\n".join(failures))
    print(f"trend: {checked} timings within {TREND_FACTOR}x of baseline")
    return checked


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="reduced matrix for CI smoke runs")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument("--out", default=os.path.join(repo_root, "BENCH_sched.json"))
    ap.add_argument("--baseline", default=os.path.join(repo_root, "BENCH_sched.json"),
                    help="committed baseline for the 2x trend gate")
    ap.add_argument("--density", type=float, default=0.10)
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the streaming executor section")
    args = ap.parse_args()

    if args.quick:
        sizes, workers, ref_max = [100, 500], [2, 4], 100
    else:
        sizes, workers, ref_max = [100, 500, 1000, 2000], [2, 4, 8], 500

    results = []
    t_all = time.perf_counter()
    equiv_checked = bench_schedulers(
        sizes, workers, args.density, ref_max, results
    )
    bench_sliced(workers, results)
    bench_grid(results)
    bench_plan_analysis(results, args.quick)
    bench_fault_recovery(results, args.quick)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from serve_chaos import bench_serve_chaos

    bench_serve_chaos(results, args.quick)

    # acceptance: ISH @ 1000 nodes / 8 workers under budget
    ish_1000_8 = [
        r for r in results
        if r["kind"] == "scheduler" and r["algo"] == "ish"
        and r["n_nodes"] == 1000 and r["n_workers"] == 8
    ]
    for r in ish_1000_8:
        assert r["schedule_s"] < ISH_1000_8_BUDGET_S, (
            f"ISH 1000/8 took {r['schedule_s']}s (budget {ISH_1000_8_BUDGET_S}s)"
        )

    # acceptance: memoized DSH stays within a small multiple of ISH
    by_algo = {
        r["algo"]: r["schedule_s"] for r in results
        if r["kind"] == "scheduler" and r["n_nodes"] == 2000
        and r["n_workers"] == 8
    }
    if "ish" in by_algo and "dsh" in by_algo:
        ratio = by_algo["dsh"] / max(by_algo["ish"], 1e-9)
        assert ratio < DSH_ISH_RATIO_BUDGET, (
            f"DSH/ISH at 2000/8 is {ratio:.1f}x (budget {DSH_ISH_RATIO_BUDGET}x)"
        )

    if not args.no_trace:
        from stream_overlap import bench_stream_overlap

        bench_stream_overlap(results, args.quick)

    # trend gate against the committed baseline, after every section has
    # appended its rows (the stream rows' staging bytes join the byte gate);
    # the baseline is read here, before --out overwrites it below
    trend_checked = check_trend(results, args.baseline)

    payload = {
        "benchmark": "sched_scale",
        "quick": args.quick,
        "density": args.density,
        "equivalence_checks": equiv_checked,
        "trend_checks": trend_checked,
        "total_s": round(time.perf_counter() - t_all, 2),
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nwrote {args.out}: {len(results)} rows, "
          f"{equiv_checked} equivalence checks, {payload['total_s']}s total")


if __name__ == "__main__":
    main()
