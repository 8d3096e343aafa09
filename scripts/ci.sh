#!/usr/bin/env bash
# Tier-1 gate: full test suite + scheduler-scaling smoke benchmark.
# Perf regressions fail loudly: sched_scale asserts fast-path/reference
# schedule equivalence, the ISH time budget, the sliced-vs-layer and
# direct-vs-tile_concat makespan wins on 8 workers, the >=2x comm-volume
# reduction of halo-aware direct edges on sliced inception, the 2-D grid
# acceptance (search_slice_factors' nested (cout x rows) tiling schedules
# <= 0.9x the best uniform single-axis tiling on TPU-priced inception(224),
# 8 workers), the fault-drill smoke (a deterministic kill campaign
# on sliced lenet5: detect -> replan m-1 -> migrate registers -> resume,
# resumed output asserted allclose to run_sequential), the serve-chaos
# smoke (a seeded Poisson trace with deadlines/backpressure through the
# sliced-plan serving frontend while a campaign kills one worker and
# straggles another mid-trace: zero request loss, dead + cordoned workers
# out of the final fleet, seed-identical replay), the stream gate (the
# buffer_depth sweep of benchmarks/stream_overlap.py: some depth >= 2
# within the staging budget must sustain >= 1.2x depth-1 supersteps/s
# through the serving frontend, or beat the absolute supersteps/s floor
# that binds on 1-core hosts where the overlap cannot materialize), and
# the trend gates against the committed BENCH_sched.json —
# 2x on scheduler/replan timings, 1.5x on sliced/grid transfer bytes,
# fault-row migrated bytes and stream-row peak staging bytes, and the
# plan-analysis row: codegen/analyze.py's happens-before analyzer proves
# the headline grid-sliced inception(64) m=8 plan hazard-free at streaming
# depth 2 with its analyze_s wall time trend-gated (the DSH/ISH
# ratio bar needs the 2000-node matrix and only runs in the full
# `make bench`).  The smoke run writes to a scratch path so the committed
# baseline is only refreshed deliberately (make bench).
#
# Plan validation: tests/conftest.py wraps build_plan so validate_plan's
# deep=True pass — structural checks (supplier liveness, register
# sizing/overlap, ring padding sentinels, tick uniformity, transfer-box
# bounds) plus the superstep-level happens-before hazard analysis — runs
# over every plan the test suite builds, original and replanned alike,
# deduplicated by content fingerprint.
#
# Trace hygiene: scripts/lint_tracehygiene.py forbids jnp fancy indexing
# and int()/float() coercions inside the scan-body/kernel trace scopes of
# codegen/ (allowlisted exceptions only).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== trace-hygiene lint (codegen/ scan-body + kernel scopes) =="
python scripts/lint_tracehygiene.py

echo "== tier-1 pytest (validate_plan deep=True over every built plan) =="
timeout 1800 python -m pytest -x -q

echo "== sched_scale smoke (--quick, trend-gated, incl. fault drill) =="
timeout 600 python benchmarks/sched_scale.py --quick \
  --out "$(mktemp -d)/BENCH_sched.json" --baseline BENCH_sched.json

echo "CI OK"
