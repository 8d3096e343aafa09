"""The cell ``googlenet.m1.closed1``: its configuration describes the full
GoogLeNet builder, the plan path matches the plain reference on the CPU, and
the cell's limits catch the control."""
import copy
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness, reference

CELL = "googlenet.m1.closed1"


def small_googlenet(hw: int = 32) -> dict:
    """``googlenet`` at a ``hw`` x ``hw`` input, every width as published."""
    cfg = copy.deepcopy(reference.load_config("googlenet"))
    cfg["input_shape"] = [hw, hw, 3]
    cfg["program"]["kwargs"]["input_hw"] = hw
    for layer in cfg["layers"]:
        if layer[0] == "avgpool":
            layer[3] = {"kernel": hw // 32, "stride": hw // 32}
    return cfg


def test_flops_per_inference():
    got = reference.flops_per_inference(reference.load_config("googlenet"))
    assert got == pytest.approx(3.165e9, rel=5e-4)


def test_configuration_describes_the_program():
    from repro.models import cnn

    cfg = reference.load_config("googlenet")
    assert len(cfg["layers"]) == 82 and cfg["reduced"] == []
    prog = cfg["program"]
    reference.check_program_matches(cfg, getattr(cnn, prog["builder"])(**prog["kwargs"]).layers)


# The cell's run at a 32x32 input on ``m`` CPU devices, through the entry as
# the benchmark builds it, but with uniform two-way slices in place of the
# slice search (which takes ~40 s on this network).
_RUN = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from repro.models import slicing
slicing.search_slice_factors = lambda model, hw, m: slicing.uniform_factors(model, 2)
from chipbench import harness
from chipbench.tests.test_chipbench_googlenet import small_googlenet
bench = harness.load_benchmark()
cell = dict(harness.load_cell(bench, {cell!r}), m={m}, chips={m})
res = harness.run_cell(bench, cell, small_googlenet(32),
                       harness.load_json("traffic", "closed1"), 2**31 + 91, 0.3,
                       False, jax.devices(), time.perf_counter(),
                       hw_kind="TPU v5 lite")
print(json.dumps(res))
"""


@pytest.mark.parametrize("m", [1, 2])
def test_plan_path_matches_the_reference(m):
    """The sliced m-worker plan, served through ``Frontend`` and the
    compiled segmented executor, answers within the cell's limits of the
    plain reference on seeded random weights. The limits lie between the
    program's own float32 rounding and the ``high`` control's error, as
    measured on the chip at 224x224 (``PERF.md``); at 32x32 on the CPU the
    program reads about half of each."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={m}")
    code = _RUN.format(root=harness.ROOT, src=os.path.join(harness.ROOT, "src"),
                       cell=CELL, m=m)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("seed", [7, 8, 2**31 + 9])
def test_control_fails_the_cell_limit(bench, seed):
    """The reference at ``high`` precision (three passes, written out) in
    the program's place reads above one of the cell's limits."""
    limits = harness.load_cell(bench, CELL)["limits"]
    cfg = small_googlenet(32)
    params = reference.make_params(cfg, seed)
    xs = reference.input_pool(cfg, 8, seed)
    ref = reference.outputs(cfg, params, xs, "highest", block=8)
    got = reference.compare(reference.outputs(cfg, params, xs, "high", block=8), ref)
    assert any(got[k] > lim for k, lim in limits.items()), got
