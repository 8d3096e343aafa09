"""The segmented executor's window gathers end to end: bit-identical to the
element gathers they replace, on the slice search's inception plans (whose
pools are channel-sliced) at m=1 and m=4, and off on lenet5, whose runs are
shorter than ``segment.MIN_WINDOW``.  Kept apart from
``test_scan_executor.py`` so that it runs beside it under pytest-xdist."""
import pytest

_SCRIPT = """
import jax, numpy as np
import repro.codegen.segment as segment
from repro.codegen import build_plan, interpret_plan
from repro.codegen.executor import build_mpmd_executor
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU, hardware_for
from repro.models.cnn import inception_net, lenet5
from repro.models.slicing import search_slice_factors, slice_model

MODEL, M = {model}, {m}
key = jax.random.PRNGKey(0)
model = MODEL
factors = search_slice_factors(model, hardware_for("TPU v5 lite"), m=M)
pools = [n for n, v in factors.items()
         if model.spec(n).op == "maxpool" and v != 1]
assert pools or model.name == "lenet5", factors
sliced = slice_model(model, factors)
sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
plan = build_plan(dsh(sdag, M), sdag)
params = model.init_params(key)
x = jax.random.normal(key, (2, *model.layers[0].out_shape))
mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:M]), ("workers",))
with_windows = segment.coalesce_windows


def run(windows=True):
    segment.coalesce_windows = (
        with_windows if windows else lambda rows, **kw: None)
    try:
        f = build_mpmd_executor(plan, sliced, params, mesh, batch=2)
    finally:
        segment.coalesce_windows = with_windows
    return np.asarray(f(x)), f.segment_stats


y, stats = run()
gathered = sum(s["gather_elems"] for s in stats)
win = sum(s["window_elems"] for s in stats)
idx = sum(s["window_indices"] for s in stats)
for s in stats:
    assert s["window_coverage"] == (
        s["window_elems"] / s["gather_elems"] if s["gather_elems"] else 0.0)
y_off, stats_off = run(windows=False)
assert sum(s["window_elems"] for s in stats_off) == 0
assert (y == y_off).all()
# interpret_plan convolves natively where the segmented kernels run
# patches + GEMM on operand weights: equal up to float reassociation
yi = np.asarray(interpret_plan(plan, sliced, params, x))
assert float(np.abs(y - yi).max()) < 1e-5
print("WINDOW_COVERAGE", win / gathered, "INDICES", idx, "ELEMS", win)
"""

CASES = {
    "inception64-m1": ("inception_net(64)", 1),
    "inception64-m4": ("inception_net(64)", 4),
    "lenet5-m1": ("lenet5(28)", 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_executor_window_path_bit_identical(subproc, case):
    """The segmented executor with window gathers against itself with them
    off and against ``interpret_plan``; on 1 or 4 virtual devices as the
    plan's m asks."""
    from repro.codegen.segment import MIN_WINDOW

    model, m = CASES[case]
    out = subproc(_SCRIPT.format(model=model, m=m), devices=m, timeout=900)
    line = next(l for l in out.splitlines() if l.startswith("WINDOW_COVERAGE"))
    _tag, cov, _i, idx, _e, elems = line.split()
    cov, idx, elems = float(cov), int(idx), int(elems)
    if case.startswith("lenet5"):
        assert cov == 0 and idx == 0, line
    else:
        assert cov > 0.5, line
        assert 0 < idx <= elems // MIN_WINDOW, line
