"""Chaos-hardened serving frontend: deadlines, backpressure, zero-loss
elastic recovery, deterministic replay (PR 8).

Everything runs on the HealthMonitor's simulated clock, so every test is
deterministic; the lenet5 m=4 frontend is rebuilt per test (state is the
thing under test) but model/params/dag are module-scoped.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import lenet5, run_sequential
from repro.models.slicing import slice_model, uniform_factors
from repro.serve import (
    Backpressure,
    ChaosCampaign,
    ChaosEvent,
    Frontend,
    FrontendConfig,
    TraceRequest,
    input_pool,
    percentile,
    poisson_trace,
)
from repro.serve.frontend import FaultEvent


@pytest.fixture(scope="module")
def lenet_setup():
    model = lenet5()
    sliced = slice_model(model, uniform_factors(model, 4))
    dag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
    params = model.init_params(jax.random.PRNGKey(0))
    pool = input_pool(model.layers[0].out_shape, 8, seed=3)
    refs = np.stack([
        np.asarray(run_sequential(sliced, params, pool[k:k + 1]))[0]
        for k in range(8)
    ])
    return model, sliced, dag, params, pool, refs


def make_frontend(setup, **cfg_kw):
    _, sliced, dag, params, _, _ = setup
    cfg = FrontendConfig(**cfg_kw) if cfg_kw else FrontendConfig()
    return Frontend(sliced, params, dag, m=4, hw=KEYSTONE_CPU, cfg=cfg)


def test_fault_free_imbalanced_plan_keeps_its_fleet():
    """A fault-free run of a load-imbalanced plan (inception's searched m=4
    DSH plan: worker 0 carries the stem) cordons no worker: stragglers are
    judged against each worker's own planned step times."""
    from repro.core.costmodel import TPU_V5E
    from repro.models.cnn import inception_net
    from repro.models.slicing import search_slice_factors

    model = inception_net(64)
    sliced = slice_model(model, search_slice_factors(model, TPU_V5E, m=4))
    dag = sliced.to_dag(TPU_V5E, time_unit=1e-6)
    params = model.init_params(jax.random.PRNGKey(0))
    fe = Frontend(sliced, params, dag, m=4, hw=TPU_V5E,
                  cfg=FrontendConfig(max_rows=1))
    loads = [sum(ts[w] for ts in fe._step_times) for w in range(4)]
    assert max(loads) > 1.2 * sorted(loads)[1]  # the plan is imbalanced
    pool = input_pool(model.layers[0].out_shape, 4, seed=3)
    trace = poisson_trace(4, seed=0, rate=0.5 / fe.est_service, rows=(1,),
                          pool_size=4, service=fe.est_service)
    fe.run_trace(trace, pool)
    assert fe.recoveries == [] and fe.fleet == (0, 1, 2, 3)
    assert fe.audit()["completed"] == 4


class TestTrace:
    def test_same_seed_same_trace(self):
        a = poisson_trace(50, seed=9, rate=0.5)
        b = poisson_trace(50, seed=9, rate=0.5)
        assert a == b
        c = poisson_trace(50, seed=10, rate=0.5)
        assert a != c

    def test_trace_shape(self):
        tr = poisson_trace(30, seed=1, rate=2.0, rows=(1, 2), pool_size=4,
                           deadline=(5.0, 10.0), service=3.0)
        assert len(tr) == 30
        arrivals = [r.arrival for r in tr]
        assert arrivals == sorted(arrivals) and arrivals[0] > 0
        assert all(r.rows in (1, 2) for r in tr)
        assert all(0 <= r.pool_idx < 4 for r in tr)
        # deadline = arrival + U(5,10)*3
        assert all(15.0 <= r.deadline - r.arrival <= 30.0 for r in tr)

    def test_percentile_nearest_rank(self):
        xs = [5.0, 1.0, 3.0, 2.0, 4.0]
        assert percentile(xs, 50) == 3.0
        assert percentile(xs, 99) == 5.0
        assert percentile([7.0], 50) == 7.0


class TestAdmission:
    def test_fault_free_drain_zero_loss(self, lenet_setup):
        fe = make_frontend(lenet_setup)
        pool, refs = lenet_setup[4], lenet_setup[5]
        trace = poisson_trace(40, seed=5, rate=2.0 / fe.est_service,
                              service=fe.est_service)
        summary = fe.run_trace(trace, pool)
        assert summary["completed"] == 40 and summary["shed"] == 0
        audit = fe.audit(ref_pool=refs)
        assert audit["zero_loss"], audit
        assert audit["max_err"] < 1e-4

    def test_backpressure_backoff_then_shed(self, lenet_setup):
        fe = make_frontend(lenet_setup, queue_limit=2, max_retries=2)
        pool = lenet_setup[4]
        far = 1e9  # deadlines never bind in this test
        reqs = [TraceRequest(i, 0.0, 1, 0, far) for i in range(6)]
        assert not isinstance(fe.submit(reqs[0], pool), Backpressure)
        assert not isinstance(fe.submit(reqs[1], pool), Backpressure)
        # queue full: structured rejection with exponential backoff
        b0 = fe.submit(reqs[2], pool)
        assert isinstance(b0, Backpressure) and b0.reason == "queue_full"
        b1 = fe.submit(reqs[2], pool)
        assert isinstance(b1, Backpressure)
        assert b1.retry_after == pytest.approx(2.0 * b0.retry_after)
        # retries exhausted: explicit shed, never a silent drop
        r2 = fe.submit(reqs[2], pool)
        assert r2.status == "shed" and r2.shed_reason == "backpressure"
        assert fe.ledger[2].retries == 2

    def test_deadline_shed_at_submit_and_in_queue(self, lenet_setup):
        fe = make_frontend(lenet_setup)
        pool = lenet_setup[4]
        est = fe._est()
        # unmeetable at submit time: now + margin*est is already past it
        r = fe.submit(TraceRequest(0, 0.0, 1, 0, 0.5 * est), pool)
        assert r.status == "shed" and r.shed_reason == "deadline"
        # meetable now, expired after the clock advances: shed in queue
        r1 = fe.submit(TraceRequest(1, 0.0, 1, 1, 2.0 * est), pool)
        assert r1.status == "queued"
        fe.monitor.advance(3.0 * est)
        fe._shed_expired()
        assert r1.status == "shed" and r1.shed_reason == "deadline"
        assert fe.audit()["zero_loss"]

    def test_oversized_request_shed(self, lenet_setup):
        fe = make_frontend(lenet_setup, max_rows=2)
        r = fe.submit(TraceRequest(0, 0.0, 3, 0, 1e9), lenet_setup[4])
        assert r.status == "shed" and r.shed_reason == "too_large"

    def test_degraded_drains_edf(self, lenet_setup):
        """Degraded mode admits one request per tick, earliest deadline
        first, and a published replan restores full admission."""
        fe = make_frontend(lenet_setup)
        pool = lenet_setup[4]
        far = 1e9
        fe.submit(TraceRequest(0, 0.0, 1, 0, far), pool)
        fe.submit(TraceRequest(1, 0.0, 1, 1, far - 5e8), pool)  # earliest
        fe.submit(TraceRequest(2, 0.0, 1, 2, far), pool)
        fe.degraded = True
        batch = fe._admit()
        assert [r.rid for r in batch] == [1]  # EDF, one per tick
        fe.degraded = False
        batch = fe._admit()
        assert sorted(r.rid for r in batch) == [0, 2]  # full admission


class TestChaos:
    def test_kill_recovery_zero_loss(self, lenet_setup):
        fe = make_frontend(lenet_setup)
        pool, refs = lenet_setup[4], lenet_setup[5]
        trace = poisson_trace(30, seed=8, rate=2.0 / fe.est_service,
                              service=fe.est_service)
        chaos = ChaosCampaign(
            events=(ChaosEvent(10, FaultEvent("kill", 2, 3)),)
        )
        summary = fe.run_trace(trace, pool, chaos=chaos)
        assert summary["completed"] + summary["shed"] == 30
        assert [r["action"] for r in fe.recoveries] == ["remesh"]
        assert 3 not in fe.fleet and fe.fleet == (0, 1, 2)
        rec = fe.recoveries[0]
        assert rec["dead_worker"] == 3 and rec["migrated_bytes"] > 0
        audit = fe.audit(ref_pool=refs)
        assert audit["zero_loss"], audit

    def test_straggler_cordoned_and_admission_recovers(self, lenet_setup):
        fe = make_frontend(lenet_setup)
        pool, refs = lenet_setup[4], lenet_setup[5]
        trace = poisson_trace(40, seed=4, rate=2.0 / fe.est_service,
                              service=fe.est_service)
        chaos = ChaosCampaign(
            events=(ChaosEvent(8, FaultEvent("straggle", 0, 2, 6.0)),)
        )
        fe.run_trace(trace, pool, chaos=chaos)
        assert "exclude_straggler" in [r["action"] for r in fe.recoveries]
        assert 2 not in fe.fleet and 2 in fe.cordoned
        # the cordoned worker is alive (it heartbeats), just out of the plan
        assert 2 in fe.monitor.alive_workers()
        # a clean fleet leaves degraded mode: full admission restored
        assert not fe.degraded
        assert fe.audit(ref_pool=refs)["zero_loss"]

    def test_kill_and_straggle_replay_identical(self, lenet_setup):
        pool, refs = lenet_setup[4], lenet_setup[5]

        def run():
            fe = make_frontend(lenet_setup)
            trace = poisson_trace(60, seed=11, rate=2.0 / fe.est_service,
                                  service=fe.est_service)
            chaos = ChaosCampaign.kill_and_straggle(60, 4, seed=7)
            fe.run_trace(trace, pool, chaos=chaos)
            return fe

        a, b = run(), run()
        assert a.fingerprint() == b.fingerprint()
        assert len(a.recoveries) == 2
        assert a.audit(ref_pool=refs)["zero_loss"]

    def test_drop_round_billed_not_lost(self, lenet_setup):
        fe = make_frontend(lenet_setup)
        pool, refs = lenet_setup[4], lenet_setup[5]
        trace = poisson_trace(12, seed=6, rate=2.0 / fe.est_service,
                              service=fe.est_service)
        chaos = ChaosCampaign(
            events=(ChaosEvent(3, FaultEvent("drop_round", 1, 1)),)
        )
        summary = fe.run_trace(trace, pool, chaos=chaos)
        assert summary["completed"] == 12
        assert fe.fleet == (0, 1, 2, 3)  # no replan for a dropped round
        assert fe.audit(ref_pool=refs)["zero_loss"]

    def test_campaign_is_deterministic(self):
        a = ChaosCampaign.kill_and_straggle(1000, 8, seed=3)
        b = ChaosCampaign.kill_and_straggle(1000, 8, seed=3)
        assert a == b
        kill, strag = a.events
        assert kill.fault.kind == "kill" and strag.fault.kind == "straggle"
        assert kill.fault.worker != strag.fault.worker
        assert kill.after_completed < strag.after_completed


class TestExecutorTick:
    def test_executor_fast_path_with_recovery(self, subproc):
        """Steady-state ticks run the compiled checkpointed executor;
        chaos ticks fall back to the interruptible runner; recovery and
        the zero-loss audit hold across the mix."""
        out = subproc("""
import numpy as np
import jax
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import lenet5, run_sequential
from repro.models.slicing import slice_model, uniform_factors
from repro.serve import Frontend, ChaosCampaign, poisson_trace, input_pool

model = lenet5()
sliced = slice_model(model, uniform_factors(model, 4))
dag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
params = model.init_params(jax.random.PRNGKey(0))
fe = Frontend(sliced, params, dag, m=4, hw=KEYSTONE_CPU)
fe.attach_executor()
pool = input_pool(model.layers[0].out_shape, 8, seed=3)
refs = np.stack([np.asarray(run_sequential(sliced, params, pool[k:k+1]))[0]
                 for k in range(8)])
trace = poisson_trace(30, seed=11, rate=2.0/fe.est_service,
                      service=fe.est_service)
chaos = ChaosCampaign.kill_and_straggle(30, 4, seed=7)
fe.run_trace(trace, pool, chaos=chaos)
audit = fe.audit(ref_pool=refs)
assert audit["zero_loss"], audit
assert fe.exec_runs > 0, "compiled fast path never used"
assert fe.exec_runs < fe.runs, "fault ticks must use the runner"
assert "remesh" in [r["action"] for r in fe.recoveries]
snaps, f = fe.last_snapshot
assert snaps.shape[0] == len(f.checkpoint_steps)
assert f.checkpoint_steps == tuple(stop for _, stop in f.segment_spans)
print("EXEC_TICK_OK", fe.exec_runs, fe.runs)
""", devices=4)
        assert "EXEC_TICK_OK" in out

    def test_executor_cache_keyed_on_knob_tuple(self, subproc):
        """Re-attaching with another ``buffer_depth`` must never reuse a
        stale compiled executor: the cache is keyed on ``(bucket,
        buffer_depth)`` and cleared on attach, and results stay
        bit-identical across depths."""
        out = subproc("""
import numpy as np
import jax
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import lenet5
from repro.models.slicing import slice_model, uniform_factors
from repro.serve import Frontend, poisson_trace, input_pool

model = lenet5()
sliced = slice_model(model, uniform_factors(model, 4))
dag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
params = model.init_params(jax.random.PRNGKey(0))
pool = input_pool(model.layers[0].out_shape, 4, seed=3)
prints = {}
keys = {}
for depth in (1, 2):
    fe = Frontend(sliced, params, dag, m=4, hw=KEYSTONE_CPU)
    fe.attach_executor(buffer_depth=depth)
    assert fe._buffer_depth == depth
    assert not fe._exec_cache, "attach must clear the cache"
    trace = poisson_trace(4, seed=5, rate=2.0 / fe.est_service,
                          service=fe.est_service)
    fe.run_trace(trace, pool)
    assert fe.exec_runs > 0 and fe.exec_runs == fe.runs
    keys[depth] = set(fe._exec_cache)
    prints[depth] = fe.fingerprint()
assert keys[1] != keys[2]
assert all(len(k) == 2 and k[1] == d for d in keys for k in keys[d]), keys
assert prints[1] == prints[2]
print("KNOB_CACHE_OK")
""", devices=4)
        assert "KNOB_CACHE_OK" in out

    def test_checkpoint_steps_matches_runner_barriers(self, subproc):
        """executor.checkpoint_steps names the superstep each snapshot is
        the entering barrier of — snaps[k] must equal the runner's barrier
        at that exact step (the contract recovery migration relies on)."""
        out = subproc("""
import numpy as np
import jax
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.codegen import build_plan, build_mpmd_executor
from repro.models.cnn import lenet5
from repro.models.slicing import slice_model, uniform_factors
from repro.runtime.faults import run_with_faults, _plan_layout

model = lenet5()
sliced = slice_model(model, uniform_factors(model, 4))
dag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
params = model.init_params(jax.random.PRNGKey(0))
plan = build_plan(dsh(dag, 4), dag)
mesh = jax.make_mesh((4,), ("workers",))
f = build_mpmd_executor(plan, sliced, params, mesh, batch=2,
                        checkpoint=True)
x = np.random.default_rng(0).standard_normal(
    (2, *model.layers[0].out_shape)).astype(np.float32)
y, snaps = f(x)
layout = _plan_layout(plan, sliced)
total = layout.total
oracle = run_with_faults(plan, sliced, params, x, layout,
                         keep_snapshots=True)
assert len(f.checkpoint_steps) == np.asarray(snaps).shape[0]
assert f.checkpoint_steps == tuple(stop for _, stop in f.segment_spans)
for k, stop in enumerate(f.checkpoint_steps):
    ref = np.stack(oracle.snapshots[stop])           # (m, batch, total)
    got = np.asarray(snaps)[k][:, :, :total]         # drop staging columns
    np.testing.assert_allclose(got, ref, atol=1e-5)
print("CKPT_STEPS_OK")
""", devices=4)
        assert "CKPT_STEPS_OK" in out

    def test_served_executor_equals_direct_build(self, subproc):
        """What ``Frontend`` serves is ``build_mpmd_executor(plan,
        checkpoint=True)`` on its plan: the same lowered program, and each
        request's output and snapshots bit for bit."""
        out = subproc("""
import math
import numpy as np
import jax
from repro.codegen import build_mpmd_executor
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import lenet5
from repro.models.slicing import slice_model, uniform_factors
from repro.serve import Frontend, FrontendConfig, TraceRequest, input_pool

model = lenet5()
sliced = slice_model(model, uniform_factors(model, 4, spatial=True))
dag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
params = model.init_params(jax.random.PRNGKey(0))
pool = input_pool(model.layers[0].out_shape, 4, seed=3)
fe = Frontend(sliced, params, dag, m=4, hw=KEYSTONE_CPU,
              cfg=FrontendConfig(max_rows=1))
fe.attach_executor(buckets=(1,))
mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("workers",))
g = build_mpmd_executor(fe.plan, sliced, params, mesh, batch=1,
                        checkpoint=True)
for rid in range(3):
    r = fe.submit(TraceRequest(rid, fe.now, 1, rid, math.inf), pool)
    fe.step()
    assert r.status == "done", r.status
    snaps, f = fe.last_snapshot
    y_g, snaps_g = g(pool[rid:rid + 1])
    assert r.output.tobytes() == np.asarray(y_g).tobytes(), rid
    assert snaps.tobytes() == np.asarray(snaps_g).tobytes(), rid
x = pool[:1]
assert f.lower(x).as_text() == g.lower(x).as_text()
assert fe.exec_runs == 3
print("SERVED_EQ_DIRECT_OK")
""", devices=4)
        assert "SERVED_EQ_DIRECT_OK" in out
