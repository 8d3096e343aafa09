"""Fault-tolerant sliced-plan runtime: deterministic fault campaigns,
superstep checkpoint/migrate/resume equivalence, plan validation, and WCET
deadline certificates."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.codegen import (
    PlanValidationError,
    RegisterLayout,
    WCETCertificate,
    build_plan,
    coalesce_transfer_steps,
    migrate_registers,
    validate_plan,
    wcet_certificate,
)
from repro.codegen.plan import Superstep, Transfer
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import inception_net, lenet5, run_sequential
from repro.models.slicing import slice_model, uniform_factors
from repro.runtime import (
    FaultEvent,
    FaultPlan,
    HealthMonitor,
    kill_and_resume_drill,
    resume_plan,
    run_with_faults,
)
from repro.runtime.faults import _plan_layout

KEY = jax.random.PRNGKey(0)


def _sliced(model_fn, factors_fn, m):
    model = model_fn()
    params = model.init_params(KEY)
    sliced = slice_model(model, factors_fn(model))
    sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
    plan = coalesce_transfer_steps(build_plan(dsh(sdag, m), sdag))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, *model.layers[0].out_shape))
    ref = np.asarray(run_sequential(model, params, x))
    return model, sliced, sdag, plan, params, x, ref


def grid_factors(model, n=4):
    f = uniform_factors(model, n, spatial=True)
    return {k: ((2, n // 2) if v == (1, n) else v) for k, v in f.items()}


# --------------------------------------------------------------------------- #
# fault campaigns: pure, seeded, replayable
# --------------------------------------------------------------------------- #
class TestFaultPlan:
    def test_same_seed_same_campaign(self):
        a = FaultPlan.random(8, 20, seed=42)
        b = FaultPlan.random(8, 20, seed=42)
        assert a == b and a.events == b.events

    def test_seeds_vary_campaigns(self):
        campaigns = {FaultPlan.random(8, 20, seed=s).events for s in range(20)}
        assert len(campaigns) > 1

    def test_kill_ends_campaign(self):
        for s in range(50):
            plan = FaultPlan.random(4, 30, seed=s)
            kills = [e for e in plan.events if e.kind == "kill"]
            if kills:
                assert plan.events[-1] == kills[0] == plan.first_kill()

    def test_at_filters_by_step(self):
        plan = FaultPlan(events=(
            FaultEvent("straggle", 1, 0, 2.0),
            FaultEvent("drop_round", 1, 2),
            FaultEvent("kill", 3, 1),
        ))
        assert len(plan.at(1)) == 2
        assert plan.at(2) == ()
        assert plan.first_kill().step == 3

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultEvent("meteor", 0, 0)


# --------------------------------------------------------------------------- #
# superstep runner: fault-free equivalence + per-kind injection semantics
# --------------------------------------------------------------------------- #
class TestRunWithFaults:
    def _fixture(self):
        return _sliced(lenet5, lambda m: uniform_factors(m, 4), 4)

    def test_no_faults_matches_sequential(self):
        _, sliced, _, plan, params, x, ref = self._fixture()
        layout = _plan_layout(plan, sliced)
        out = run_with_faults(plan, sliced, params, x, layout)
        assert out.status == "ok"
        np.testing.assert_allclose(np.asarray(out.output), ref,
                                   atol=1e-4, rtol=1e-4)

    def test_kill_returns_entering_barrier(self):
        _, sliced, _, plan, params, x, _ = self._fixture()
        layout = _plan_layout(plan, sliced)
        out = run_with_faults(plan, sliced, params, x, layout,
                              faults=FaultPlan.single_kill(2, 1))
        assert out.status == "killed" and out.step == 2
        assert out.output is None and out.fault.worker == 1
        snap = out.snapshot
        assert len(snap) == plan.n_workers
        assert all(b.shape == (1, layout.total) for b in snap)

    def test_straggle_slows_but_stays_correct(self):
        _, sliced, sdag, plan, params, x, ref = self._fixture()
        layout = _plan_layout(plan, sliced)
        mon = HealthMonitor(4, heartbeat_timeout=1e9)
        faults = FaultPlan(events=(FaultEvent("straggle", 0, 2, 8.0),))
        out = run_with_faults(plan, sliced, params, x, layout,
                              faults=faults, monitor=mon, dag=sdag)
        assert out.status == "ok" and out.straggled == {2: 8.0}
        np.testing.assert_allclose(np.asarray(out.output), ref,
                                   atol=1e-4, rtol=1e-4)
        # the simulated clock fed the monitor per-step, per-worker timings
        assert all(len(mon.workers[w].timings) == len(plan.steps)
                   for w in range(4))

    def test_drop_round_bills_retransmission(self):
        _, sliced, _, plan, params, x, ref = self._fixture()
        layout = _plan_layout(plan, sliced)
        step = next(i for i, s in enumerate(plan.steps) if s.transfers)
        faults = FaultPlan(events=(FaultEvent("drop_round", step, 0),))
        out = run_with_faults(plan, sliced, params, x, layout, faults=faults)
        # retry re-ships the round: billed, but numerically invisible
        assert out.status == "ok" and out.retransmitted_bytes > 0
        np.testing.assert_allclose(np.asarray(out.output), ref,
                                   atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------- #
# migrate_registers property sweep: kill anywhere, resume matches sequential
# --------------------------------------------------------------------------- #
class TestMigrateResumeProperty:
    CASES = {
        "lenet5-channel": (lenet5, lambda m: uniform_factors(m, 4)),
        "lenet5-rows": (lenet5, lambda m: uniform_factors(m, 4, spatial=True)),
        "lenet5-grid": (lenet5, grid_factors),
        "inception-channel": (lambda: inception_net(64),
                              lambda m: uniform_factors(m, 4)),
        "inception-grid": (lambda: inception_net(64), grid_factors),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_kill_resume_allclose(self, case):
        model_fn, factors_fn = self.CASES[case]
        m = 4
        _, sliced, sdag, plan, params, x, ref = _sliced(model_fn, factors_fn, m)
        new_plan = coalesce_transfer_steps(build_plan(dsh(sdag, m - 1), sdag))
        layout = _plan_layout(plan, sliced)
        new_layout = _plan_layout(new_plan, sliced)
        n = len(plan.steps)
        rng = np.random.default_rng(7)
        steps = sorted({1, n // 2, n - 1, int(rng.integers(1, n))})
        for k in steps:
            w = int(rng.integers(m))
            out = run_with_faults(plan, sliced, params, x, layout,
                                  faults=FaultPlan.single_kill(k, w))
            assert out.status == "killed" and out.step == k
            bufs, completed, stats = migrate_registers(
                plan, new_plan, layout, new_layout, out.snapshot, k)
            assert stats["resumed_from_step"] == k
            res = resume_plan(new_plan, sliced, params, x, new_layout,
                              bufs, completed)
            assert res.status == "ok", (case, k, w)
            np.testing.assert_allclose(np.asarray(res.output), ref,
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"{case} kill@{k}/w{w}")

    def test_migration_stats_monotone(self):
        """Later kills complete more nodes and migrate at least as many
        placements' worth of state."""
        _, sliced, sdag, plan, params, x, _ = _sliced(
            lenet5, lambda m: uniform_factors(m, 4), 4)
        new_plan = coalesce_transfer_steps(build_plan(dsh(sdag, 3), sdag))
        layout = _plan_layout(plan, sliced)
        new_layout = _plan_layout(new_plan, sliced)
        done = []
        for k in range(1, len(plan.steps)):
            out = run_with_faults(plan, sliced, params, x, layout,
                                  faults=FaultPlan.single_kill(k, 0))
            _, completed, stats = migrate_registers(
                plan, new_plan, layout, new_layout, out.snapshot, k)
            assert stats["completed_nodes"] == len(completed)
            done.append(stats["completed_nodes"])
        assert done == sorted(done) and done[-1] > done[0]


# --------------------------------------------------------------------------- #
# headline drill: grid-sliced inception(64), kill mid-run, replan to m-1
# --------------------------------------------------------------------------- #
class TestKillAndResumeDrill:
    def test_headline_inception_grid(self):
        model = inception_net(64)
        params = model.init_params(KEY)
        base = uniform_factors(model, 8, spatial=True)
        factors = {k: ((2, 4) if v == (1, 8) else v) for k, v in base.items()}
        sliced = slice_model(model, factors)
        sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (1, *model.layers[0].out_shape))
        drill = kill_and_resume_drill(sliced, params, x, sdag, m=8,
                                      kill_step=4, kill_worker=3,
                                      hw=KEYSTONE_CPU)
        ref = run_sequential(model, params, x)
        np.testing.assert_allclose(np.asarray(drill["output"]),
                                   np.asarray(ref), atol=1e-4, rtol=1e-4)
        assert drill["detected"]
        assert drill["new_plan"].n_workers == 7
        assert drill["recomputed_supersteps"] <= 1
        assert drill["migrated_bytes"] > 0 and drill["placements"] > 0
        # the degraded plan ships re-certified
        cert = drill["certificate"]
        assert cert is not None
        assert cert.n_steps == len(drill["new_plan"].steps)
        assert cert.total >= drill["new_plan"].makespan

    def test_seeded_kill_is_deterministic(self):
        _, sliced, sdag, _, params, x, ref = _sliced(
            lenet5, lambda m: uniform_factors(m, 4), 4)
        a = kill_and_resume_drill(sliced, params, x, sdag, m=4, seed=3)
        b = kill_and_resume_drill(sliced, params, x, sdag, m=4, seed=3)
        assert (a["kill_step"], a["kill_worker"]) == (b["kill_step"],
                                                     b["kill_worker"])
        np.testing.assert_allclose(np.asarray(a["output"]),
                                   np.asarray(b["output"]))
        np.testing.assert_allclose(np.asarray(a["output"]), ref,
                                   atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------- #
# checkpointing executor: barrier carries match the superstep runner
# --------------------------------------------------------------------------- #
class TestCheckpointExecutor:
    def test_checkpoint_snapshots_match_runner(self, subproc):
        out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.codegen import build_plan, coalesce_transfer_steps
from repro.codegen.executor import build_mpmd_executor
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import lenet5, run_sequential
from repro.models.slicing import slice_model, uniform_factors
from repro.runtime.faults import _plan_layout, run_with_faults

m, batch = 4, 2
key = jax.random.PRNGKey(0)
mesh = jax.make_mesh((m,), ("workers",))
model = lenet5()
params = model.init_params(key)
x = jax.random.normal(jax.random.PRNGKey(1),
                      (batch, *model.layers[0].out_shape))
ref = run_sequential(model, params, x)
sliced = slice_model(model, uniform_factors(model, m))
sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
plan = coalesce_transfer_steps(build_plan(dsh(sdag, m), sdag))
f = build_mpmd_executor(plan, sliced, params, mesh, batch=batch,
                        checkpoint=True)
y, snaps = f(x)
assert float(jnp.abs(y - ref).max()) < 1e-4
total = f.layout.total
# width = registers + sentinel regions + dump col + comm staging strips
assert f.width >= total + 3
assert snaps.shape == (len(f.segment_spans), m, batch, f.width)

# oracle: the numpy superstep runner with every barrier retained
layout = _plan_layout(plan, sliced)
assert dict(layout.offsets) == dict(f.layout.offsets)
assert layout.total == total
oracle = run_with_faults(plan, sliced, params, x, layout,
                         keep_snapshots=True)
assert oracle.status == "ok"
for k, (start, stop) in enumerate(f.segment_spans):
    want = np.stack(oracle.snapshots[stop])           # (m, batch, total)
    got = np.asarray(snaps[k][:, :, :total])
    err = np.abs(got - want).max()
    assert err < 1e-4, (k, start, stop, err)
print("CKPT_EQUIV_OK")
""", devices=4)
        assert "CKPT_EQUIV_OK" in out


# --------------------------------------------------------------------------- #
# validate_plan: valid plans pass, hand-broken plans fail loudly
# --------------------------------------------------------------------------- #
class TestValidatePlan:
    def _plan(self):
        _, sliced, sdag, plan, _, _, _ = _sliced(
            lenet5, lambda m: uniform_factors(m, 4), 4)
        return sliced, sdag, plan

    def test_valid_plan_passes_with_stats(self):
        sliced, sdag, plan = self._plan()
        stats = validate_plan(plan, sdag, model=sliced)
        assert stats["supersteps"] == len(plan.steps)
        assert stats["transfers"] > 0
        assert stats["packed_elements"] > 0

    def test_transfer_before_compute_rejected(self):
        sliced, sdag, plan = self._plan()
        t = next(t for s in plan.steps for t in s.transfers)
        early = dataclasses.replace(
            plan.steps[0],
            transfers=(Transfer(t.node, t.src, t.dst, t.box),))
        bad = dataclasses.replace(plan, steps=(early,) + plan.steps[1:])
        with pytest.raises(PlanValidationError):
            validate_plan(bad, sdag)

    def test_out_of_range_endpoint_rejected(self):
        _, sdag, plan = self._plan()
        i, t = next((i, t) for i, s in enumerate(plan.steps)
                    for t in s.transfers)
        broken = dataclasses.replace(
            plan.steps[i],
            transfers=(dataclasses.replace(t, dst=plan.n_workers + 1),))
        bad = dataclasses.replace(
            plan, steps=plan.steps[:i] + (broken,) + plan.steps[i + 1:])
        with pytest.raises(PlanValidationError):
            validate_plan(bad, sdag)

    def test_degenerate_box_rejected(self):
        _, sdag, plan = self._plan()
        i, t = next((i, t) for i, s in enumerate(plan.steps)
                    for t in s.transfers)
        broken = dataclasses.replace(
            plan.steps[i],
            transfers=(dataclasses.replace(t, box=((5, 3),)),))
        bad = dataclasses.replace(
            plan, steps=plan.steps[:i] + (broken,) + plan.steps[i + 1:])
        with pytest.raises(PlanValidationError):
            validate_plan(bad, sdag)

    def test_oversized_box_rejected(self):
        sliced, sdag, plan = self._plan()
        i, t = next((i, t) for i, s in enumerate(plan.steps)
                    for t in s.transfers)
        extent = sliced.spec(t.node).out_shape[0]
        broken = dataclasses.replace(
            plan.steps[i],
            transfers=(dataclasses.replace(t, box=((0, extent + 64),)),))
        bad = dataclasses.replace(
            plan, steps=plan.steps[:i] + (broken,) + plan.steps[i + 1:])
        with pytest.raises(PlanValidationError):
            validate_plan(bad, sdag, model=sliced)

    def test_missing_compute_rejected(self):
        _, sdag, plan = self._plan()
        # drop every compute of the sink: the plan never produces its output
        steps = tuple(
            dataclasses.replace(s, compute=tuple(
                tuple(n for n in seg if n != plan.sink) for seg in s.compute))
            for s in plan.steps)
        bad = dataclasses.replace(plan, steps=steps)
        with pytest.raises(PlanValidationError):
            validate_plan(bad, sdag)

    def test_double_compute_rejected(self):
        _, sdag, plan = self._plan()
        i, w, seg = next((i, w, seg) for i, s in enumerate(plan.steps)
                         for w, seg in enumerate(s.compute) if seg)
        dup = tuple(
            (s + (s[-1],)) if j == w else s
            for j, s in enumerate(plan.steps[i].compute))
        broken = dataclasses.replace(plan.steps[i], compute=dup)
        bad = dataclasses.replace(
            plan, steps=plan.steps[:i] + (broken,) + plan.steps[i + 1:])
        with pytest.raises(PlanValidationError):
            validate_plan(bad, sdag)


# --------------------------------------------------------------------------- #
# WCET certificates
# --------------------------------------------------------------------------- #
class TestWCETCertificate:
    def _cert(self, margin=1.0):
        _, sliced, sdag, plan, _, _, _ = _sliced(
            lenet5, lambda m: uniform_factors(m, 4), 4)
        out_bytes = {l.name: float(np.prod(l.out_shape)) * 4
                     for l in sliced.layers}
        return plan, wcet_certificate(plan, sdag, out_bytes,
                                      hw=KEYSTONE_CPU, margin=margin)

    def test_certificate_covers_makespan(self):
        plan, cert = self._cert()
        assert cert.n_steps == len(plan.steps)
        assert all(b >= 0 for b in cert.step_bounds)
        # a barrier-synchronized bound can only be looser than the
        # overlapped schedule it certifies — but not vacuously so
        assert plan.makespan <= cert.total <= 10 * plan.makespan

    def test_margin_scales_bounds(self):
        _, base = self._cert()
        _, derated = self._cert(margin=2.0)
        assert derated.total == pytest.approx(2 * base.total, rel=1e-9)

    def test_requires_pricing(self):
        _, _, sdag, plan, _, _, _ = _sliced(
            lenet5, lambda m: uniform_factors(m, 4), 4)
        with pytest.raises(ValueError, match="hw|comm_time"):
            wcet_certificate(plan, sdag, {})

    def test_overruns_attribution_and_slack(self):
        cert = WCETCertificate(compute_bounds=(1.0, 2.0),
                               comm_bounds=(0.5, 0.5))
        assert cert.bound(0) == 1.5 and cert.bound(1) == 2.5
        timings = [(0, 2.0), (1, 2.0), (5, 99.0), (-1, 99.0)]
        assert cert.overruns(timings) == [(0, 2.0)]
        assert cert.overruns(timings, slack=2.0) == []

    def test_hardware_derate(self):
        hw = KEYSTONE_CPU.derate(2.0)
        assert hw.peak_flops == KEYSTONE_CPU.peak_flops / 2
        assert hw.hbm_bw == KEYSTONE_CPU.hbm_bw / 2
        assert hw.ici_latency == KEYSTONE_CPU.ici_latency * 2
        assert "derated-2x" in hw.name
        with pytest.raises(ValueError):
            KEYSTONE_CPU.derate(0.0)
        _, _, sdag, plan, _, _, _ = _sliced(
            lenet5, lambda m: uniform_factors(m, 4), 4)
        out_bytes = {n: 4096.0 for n in sdag.nodes}
        slow = wcet_certificate(plan, sdag, out_bytes, hw=hw)
        fast = wcet_certificate(plan, sdag, out_bytes, hw=KEYSTONE_CPU)
        assert slow.total > fast.total


# --------------------------------------------------------------------------- #
# checkpoint snapshots are invariant under the buffer depth, and every
# depth's snapshot resumes correctly after a kill at any segment boundary
# --------------------------------------------------------------------------- #
class TestCheckpointKnobInvariance:
    def test_snapshots_bit_identical_across_knobs_and_resume(self, subproc):
        out = subproc("""
import itertools
import jax, jax.numpy as jnp, numpy as np
from repro.codegen import build_plan, coalesce_transfer_steps, \
    migrate_registers
from repro.codegen.executor import build_mpmd_executor
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import lenet5, run_sequential
from repro.models.slicing import slice_model, uniform_factors
from repro.runtime.faults import _plan_layout, resume_plan

m, batch = 4, 2
key = jax.random.PRNGKey(0)
mesh = jax.make_mesh((m,), ("workers",))
model = lenet5()
params = model.init_params(key)
x = jax.random.normal(jax.random.PRNGKey(1),
                      (batch, *model.layers[0].out_shape))
ref = np.asarray(run_sequential(model, params, x))
sliced = slice_model(model, uniform_factors(model, m, spatial=True))
sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
plan = coalesce_transfer_steps(build_plan(dsh(sdag, m), sdag))
layout = _plan_layout(plan, sliced)
total = layout.total

# option matrix: snapshots (and output) bit-identical in the register
# region.  buffer_depth >= 2 streams deliveries through rotating staging
# frames and donates the carry, but retire-on-evict materializes every live
# value back into its packed column before a frame rotates — snapshots
# [:total] must stay byte-equal to the depth-1 (write-once staging)
# executor, and the output must not depend on whether snapshots are taken.
ref_y = ref_snaps = spans = None
for depth, ckpt in itertools.product((1, 2, 4), (True, False)):
    f = build_mpmd_executor(plan, sliced, params, mesh, batch=batch,
                            checkpoint=ckpt, buffer_depth=depth)
    if not ckpt:
        assert (np.asarray(f(x)) == ref_y).all(), depth
        continue
    y, snaps = f(x)
    regs = np.asarray(snaps[:, :, :, :total])
    if ref_y is None:
        ref_y, ref_snaps, spans = np.asarray(y), regs, f.segment_spans
    else:
        assert (np.asarray(y) == ref_y).all(), depth
        assert f.segment_spans == spans, depth
        assert (regs == ref_snaps).all(), depth
    if depth == 4:
        stream_snaps = regs

# kill x resume drill: each boundary snapshot of the *streamed* (depth-4)
# executor restarts the numpy runner on the same plan and still reaches the
# reference output — a kill at any barrier never observes in-flight frames
for k, (start, stop) in enumerate(spans[:-1]):
    bufs = [stream_snaps[k, w] for w in range(m)]
    done = {n for s in plan.steps[:stop] for seg in s.compute for n in seg}
    res = resume_plan(plan, sliced, params, x, layout, bufs, done)
    assert res.status == "ok", (k, stop)
    np.testing.assert_allclose(np.asarray(res.output), ref,
                               atol=1e-4, rtol=1e-4,
                               err_msg=f"resume from segment {k}")
print("CKPT_KNOB_OK")
""", devices=4)
        assert "CKPT_KNOB_OK" in out
