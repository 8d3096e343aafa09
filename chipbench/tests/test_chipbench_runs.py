"""Whole runs of a cell on the CPU, the chip check skipped: a sound run is
correct, and each fault planted in the timed path makes it incorrect."""
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from chipbench import harness, reference

ROOT = harness.ROOT
HW = "TPU v5 lite"  # the plan is priced as on the chip


def _run(bench, workload, seed=2**31 + 77, seconds=0.5):
    cell = harness.load_cell(bench, workload)
    cfg = reference.load_config(cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    return harness.run_cell(bench, cell, cfg, traffic, seed, seconds, False,
                            jax.devices(), time.perf_counter(), hw_kind=HW)


@pytest.fixture
def highest():
    yield
    jax.config.update("jax_default_matmul_precision", None)


def _wrap_executor(monkeypatch, alter):
    """Plant ``alter(y, call) -> y`` on every output the executor returns."""
    from repro.codegen import executor

    build = executor.build_mpmd_executor

    def patched(*a, **k):
        f = build(*a, **k)
        calls = []

        def g(x):
            y, snaps = f(x)
            calls.append(None)
            return alter(np.asarray(y), len(calls)), snaps

        return g

    monkeypatch.setattr(executor, "build_mpmd_executor", patched)


def test_sound_run_is_correct(bench, highest):
    res = _run(bench, "lenet5.m1.closed1")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 10
    assert set(res["metrics"]) == {"latency_p50_ms", "latency_p95_ms", "inferences_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_inception_plan_run_is_correct(bench, highest):
    """The inception224 cell's m=1 plan path, at a 32x32 input."""
    cell = harness.load_cell(bench, "inception224.m1.closed1")
    from chipbench.tests.conftest import small_inception

    res = harness.run_cell(bench, cell, small_inception(32), harness.load_json("traffic", "closed1"),
                           5, 0.3, False, jax.devices(), time.perf_counter(), hw_kind=HW)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_altered_answer_is_incorrect(bench, monkeypatch, highest):
    def alter(y, call):
        y = y.copy()
        y[0, int(np.argmax(np.abs(y[0])))] *= 1.001
        return y

    _wrap_executor(monkeypatch, alter)
    assert not _run(bench, "lenet5.m1.closed1")["correct"]


def test_stale_answer_is_incorrect(bench, monkeypatch, highest):
    """A step that hands back its first answer unchanged."""
    first = []

    def stale(y, call):
        if not first:
            first.append(y)
        return first[0]

    _wrap_executor(monkeypatch, stale)
    assert not _run(bench, "lenet5.m1.closed1")["correct"]


def test_failing_requests_are_incorrect(bench, monkeypatch, highest):
    def boom(y, call):
        if call > 3:  # warm-up passes, the window fails
            raise RuntimeError("planted")
        return y

    _wrap_executor(monkeypatch, boom)
    res = _run(bench, "lenet5.m1.closed1")
    assert not res["correct"] and res["failed"] > 0


_FOUR = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
from chipbench import harness
from chipbench.tests.conftest import small_inception
if {fault!r} == "exchange":
    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
bench = harness.load_benchmark()
cell = dict(harness.load_cell(bench, "inception224.m1.closed1"), m=4, chips=4)
res = harness.run_cell(bench, cell, small_inception(32),
                       harness.load_json("traffic", "closed1"), 123, 0.5, False,
                       jax.devices(), time.perf_counter(), hw_kind={hw!r})
print(json.dumps(res))
"""


@pytest.mark.parametrize("fault,correct", [("none", True), ("exchange", False)])
def test_four_worker_plan_exchange(fault, correct):
    """The m=4 plan of inception224 at a 32x32 input on four CPU devices
    (16 transfers): sound, then with every ppermute delivering zeros (the
    exchange between chips left out)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR.format(root=ROOT, src=os.path.join(ROOT, "src"), fault=fault, hw=HW)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    import json

    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is correct, res["checks"]


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "lenet5.m1.closed1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "device: platform=cpu" in out.stderr
