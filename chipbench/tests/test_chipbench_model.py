"""The benchmark's model arithmetic: FLOP counts, the plain reference
against the program's sequential code, and the control against the limits."""
import json

import jax
import numpy as np
import pytest

from chipbench import harness, reference
from chipbench.tests.conftest import small_inception


@pytest.mark.parametrize("name,flops", [("inception224", 1.794e9), ("lenet5", 1.386e6)])
def test_flops_per_inference(name, flops):
    got = reference.flops_per_inference(reference.load_config(name))
    assert got == pytest.approx(flops, rel=5e-4)


def _program(cfg):
    from repro.models import cnn

    prog = cfg["program"]
    return getattr(cnn, prog["builder"])(**prog["kwargs"])


@pytest.mark.parametrize("name", ["inception224", "lenet5"])
def test_configuration_describes_the_program(name):
    cfg = reference.load_config(name)
    reference.check_program_matches(cfg, _program(cfg).layers)


def test_a_configuration_that_differs_is_refused():
    cfg = json.loads(json.dumps(reference.load_config("lenet5")))
    cfg["layers"][0][3]["features"] = 8
    with pytest.raises(ValueError):
        reference.check_program_matches(cfg, _program(reference.load_config("lenet5")).layers)


@pytest.mark.parametrize("name", ["inception224", "lenet5"])
def test_reference_matches_run_sequential_on_cpu(name):
    from repro.models.cnn import run_sequential

    cfg = reference.load_config(name)
    params = reference.make_params(cfg, 2**33 + 5)
    xs = reference.input_pool(cfg, 2, seed=9)
    want = np.asarray(jax.jit(lambda p, x: run_sequential(_program(cfg), p, x))(params, xs))
    got = reference.outputs(cfg, params, xs, "highest", block=2)
    assert reference.rel_errors(got, want).max() < 1e-5


@pytest.mark.parametrize("workload", ["lenet5.m1.closed1", "inception224.m1.closed1"])
def test_control_fails_the_cell_limit(bench, workload):
    """The reference at ``high`` precision (three passes, written out) in
    the program's place reads above one of the cell's limits, on three
    seeds."""
    cell = harness.load_cell(bench, workload)
    cfg = reference.load_config(cell["config"])
    if cell["config"] == "inception224":
        cfg = small_inception(32)
    for seed in (1, 2, 2**31 + 3):
        params = reference.make_params(cfg, seed)
        xs = reference.input_pool(cfg, 8, seed)
        ref = reference.outputs(cfg, params, xs, "highest", block=8)
        ctl = reference.outputs(cfg, params, xs, "high", block=8)
        got = reference.compare(ctl, ref)
        assert any(got[k] > lim for k, lim in cell["limits"].items()), got


def test_rel_errors_reads_non_finite_as_inf():
    ys = np.array([[1.0, np.nan], [1.0, 2.0]])
    refs = np.array([[1.0, 2.0], [1.0, 2.5]])
    assert list(reference.rel_errors(ys, refs)) == [np.inf, 0.2]


def test_compare():
    refs = np.array([[3.0, 4.0], [0.0, 1.0]])
    ys = refs + np.array([[0.0, 0.5], [0.0, 0.0]])
    got = reference.compare(ys, refs)
    assert got["max_rel_err"] == pytest.approx(0.125)
    assert got["rms_rel_err"] == pytest.approx(np.sqrt(0.1 ** 2 / 2))
    assert reference.compare(np.zeros((0, 2)), np.zeros((0, 2)))["max_rel_err"] == np.inf
    assert reference.compare(np.array([[np.nan, 1.0]]), refs[:1]) == {
        "max_rel_err": np.inf, "rms_rel_err": np.inf}


def test_weights_and_inputs_follow_the_seed():
    cfg = reference.load_config("lenet5")
    a, b = reference.make_params(cfg, 2**32 + 7), reference.make_params(cfg, 2**32 + 7)
    c = reference.make_params(cfg, 7)
    assert all(np.array_equal(a[k]["w"], b[k]["w"]) for k in a)
    assert not np.array_equal(a["conv1"]["w"], c["conv1"]["w"])
    assert np.array_equal(reference.input_pool(cfg, 4, 3), reference.input_pool(cfg, 4, 3))
