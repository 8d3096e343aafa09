"""Landing runs of the segmented executor (``segment.land_runs``): the cut
itself, and the executor built with it against one scan per segment —
outputs and checkpoint snapshots bit-identical, on the slice search's
inception plans at m=1 and m=4 and on lenet5."""
import jax
import numpy as np
import pytest

import repro.codegen.segment as segment
from repro.codegen.segment import LAND_SCAN_COST, land_runs

# the ticks' widest outputs (columns) of the served inception_net(224) m=1
# plan: DSH over the slice search's factors for a v5e, one segment, one
# node per tick
INCEPTION224_M1 = [
    150528, 802816, 100352, 100352, 301056, 301056, 75264, 75264, 37632,
    37632, 75264, 75264, 50176, 50176, 6272, 6272, 25088, 25088, 12544,
    12544, 12544, 12544, 50176, 50176, 100352, 100352, 75264, 75264, 12544,
    12544, 50176, 50176, 25088, 25088, 37632, 37632, 120, 120, 120, 120,
    480, 480, 10, 10,
]
LENET5_M1 = [784, 4704, 392, 392, 196, 196, 1568, 1568, 196, 196, 196, 196,
             784, 784, 120, 84, 10, 10]

WIDTHS = {
    "inception224-m1": INCEPTION224_M1,
    "lenet5-m1": LENET5_M1,
    "rising": [1, 2, 4, 8, 16, 32, 64, 128],
    "one-wide": [5, 5, 900, 5, 5, 5, 5, 5],
    "idle-ticks": [0, 0, 300, 0, 40, 40, 0],
    "one-tick": [7],
}
COSTS = (0, 1, 50, 50_176, LAND_SCAN_COST, 10**12)


def _land_elems(widths, runs):
    return sum((b - a) * max([1] + widths[a:b]) for a, b in runs)


@pytest.mark.parametrize("cost", COSTS)
@pytest.mark.parametrize("case", sorted(WIDTHS))
def test_land_runs_cut(case, cost):
    """Runs are contiguous and cover every tick once; landing at each
    run's width never writes more than the segment's widest register on
    every tick; a cut that saves no more than its scan cost is not made;
    and the DP's total is no worse than one run or one run per tick."""
    widths = WIDTHS[case]
    runs = land_runs(widths, cost)
    n = len(widths)
    assert runs[0][0] == 0 and runs[-1][1] == n
    assert all(a < b for a, b in runs)
    assert all(runs[k][1] == runs[k + 1][0] for k in range(len(runs) - 1))
    whole = n * max([1] + widths)
    elems = _land_elems(widths, runs)
    assert elems <= whole
    total = elems + cost * (len(runs) - 1)
    assert total <= whole
    ticks = [(t, t + 1) for t in range(n)]
    assert total <= _land_elems(widths, ticks) + cost * (n - 1)
    if len(runs) > 1:
        assert whole - elems > cost * (len(runs) - 1)


@pytest.mark.parametrize("cost", COSTS)
@pytest.mark.parametrize("width", [1, 128, 802816])
def test_land_runs_equal_widths_one_run(width, cost):
    assert land_runs([width] * 37, cost) == ((0, 37),)


def test_land_runs_inception224_m1():
    """At the chosen per-scan cost the served inception224 m=1 plan lands
    at most 0.3 of what one scan at its widest register lands."""
    runs = land_runs(INCEPTION224_M1, LAND_SCAN_COST)
    assert len(runs) > 1
    whole = len(INCEPTION224_M1) * max(INCEPTION224_M1)
    assert _land_elems(INCEPTION224_M1, runs) <= 0.3 * whole


def test_lenet5_one_run_per_segment_same_program(monkeypatch):
    """lenet5's served m=1 plan keeps one scan per segment at the chosen
    cost: no cut saves more than a scan costs there, and the lowered
    program equals a build with the cut disabled."""
    from repro.codegen import build_plan
    from repro.codegen.executor import build_mpmd_executor
    from repro.core import dsh
    from repro.core.costmodel import hardware_for
    from repro.models.cnn import lenet5
    from repro.models.slicing import search_slice_factors, slice_model

    hw = hardware_for("TPU v5 lite")
    model = lenet5(28)
    sliced = slice_model(model, search_slice_factors(model, hw, m=1))
    dag = sliced.to_dag(hw, time_unit=1e-6)
    plan = build_plan(dsh(dag, 1), dag)
    params = model.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 28, 28, 1))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("workers",))

    def build():
        return build_mpmd_executor(plan, sliced, params, mesh, batch=1,
                                   checkpoint=True)

    f = build()
    assert all(s["land_runs"] == 1 for s in f.segment_stats)
    monkeypatch.setattr(segment, "LAND_SCAN_COST", 10**12)
    g = build()
    assert f.lower(x).as_text() == g.lower(x).as_text()


_SCRIPT = """
import jax, numpy as np
import repro.codegen.segment as segment
from repro.codegen import build_plan
from repro.codegen.executor import build_mpmd_executor
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU, hardware_for
from repro.models.cnn import inception_net, lenet5
from repro.models.slicing import search_slice_factors, slice_model

MODEL, M, DEPTH = {model}, {m}, {depth}
key = jax.random.PRNGKey(0)
model = MODEL
factors = search_slice_factors(model, hardware_for("TPU v5 lite"), m=M)
sliced = slice_model(model, factors)
sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
plan = build_plan(dsh(sdag, M), sdag)
params = model.init_params(key)
x = jax.random.normal(key, (2, *model.layers[0].out_shape))
mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:M]), ("workers",))
chosen = segment.LAND_SCAN_COST


def run(scan_cost):
    segment.LAND_SCAN_COST = scan_cost
    try:
        f = build_mpmd_executor(plan, sliced, params, mesh, batch=2,
                                checkpoint=True, buffer_depth=DEPTH)
    finally:
        segment.LAND_SCAN_COST = chosen
    y, snaps = f(x)
    return f, np.asarray(y), np.asarray(snaps)


cut, y_cut, s_cut = run(0)
one, y_one, s_one = run(10**12)
assert all(s["land_runs"] == 1 for s in one.segment_stats)
assert sum(s["land_runs"] for s in cut.segment_stats) > len(cut.segment_stats)
for a, b in zip(cut.segment_stats, one.segment_stats):
    assert a["land_elems"] <= b["land_elems"]
    assert b["land_efficiency"] <= a["land_efficiency"] <= 1.0
assert cut.checkpoint_steps == one.checkpoint_steps
assert cut.segment_spans == one.segment_spans
assert cut.width == one.width
assert y_cut.tobytes() == y_one.tobytes()
assert s_cut.shape == s_one.shape and s_cut.tobytes() == s_one.tobytes()
print("LAND_RUNS", [s["land_runs"] for s in cut.segment_stats])
"""

CASES = {
    "inception64-m1": ("inception_net(64)", 1, 1),
    "inception64-m4": ("inception_net(64)", 4, 1),
    "inception64-m4-depth2": ("inception_net(64)", 4, 2),
    "lenet5-m1": ("lenet5(28)", 1, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_land_runs_bit_identical(subproc, case):
    """The executor cut into a scan per landing run (per-scan cost 0)
    against one scan per segment (cost past any saving): outputs and
    every checkpoint snapshot bit-identical, the same checkpoint steps
    and segment spans; on 1 or 4 virtual devices as the plan's m asks."""
    model, m, depth = CASES[case]
    out = subproc(_SCRIPT.format(model=model, m=m, depth=depth),
                  devices=m, timeout=900)
    assert "LAND_RUNS" in out
