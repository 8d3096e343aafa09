"""Integration: training loop, serving engine, fault tolerance."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.configs import get_config
from repro.data import SyntheticLMDataset
from repro.models import forward, init_params
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import ElasticPlanner, HealthMonitor, simulate_failure_recovery
from repro.serve import Engine, ServeConfig
from repro.train import TrainConfig, Trainer, make_train_step

CFG = get_config("qwen2-0.5b").reduced()
OPT = AdamWConfig(lr=5e-3, warmup_steps=5, total_steps=500)


def _trainer(tmp=None, **kw):
    ds = SyntheticLMDataset(CFG.vocab, seq_len=48, global_batch=4, seed=0)
    ckpt = CheckpointManager(tmp, keep=2) if tmp else None
    return Trainer(CFG, TrainConfig(microbatches=1, remat=False, optim=OPT),
                   ds, ckpt_manager=ckpt, **kw)


class TestTraining:
    def test_loss_decreases(self):
        tr = _trainer()
        out = tr.run(25, log_every=0)
        assert out["final_loss"] < tr.history[0]["loss"] - 0.3

    def test_microbatch_equivalence(self):
        ds = SyntheticLMDataset(CFG.vocab, seq_len=32, global_batch=8, seed=1)
        b = ds.batch(0)
        feed = {"tokens": jnp.asarray(b.inputs), "labels": jnp.asarray(b.labels)}
        params = init_params(CFG, jax.random.PRNGKey(0))
        outs = []
        for acc in (1, 4):
            tc = TrainConfig(microbatches=acc, remat=(acc > 1), optim=OPT)
            step = jax.jit(make_train_step(CFG, tc))
            p, _, m = step(params, adamw_init(params, OPT), feed)
            outs.append((m["loss"], p))
        assert float(outs[0][0]) == pytest.approx(float(outs[1][0]), rel=1e-4)

    def test_checkpoint_resume_continues(self, tmp_path):
        res = simulate_failure_recovery(
            lambda: _trainer(str(tmp_path), ckpt_every=5),
            fail_at_step=12, total_steps=20, ckpt_every=5,
        )
        assert res["resumed"] and res["resume_step"] == 10
        pre = res["pre_crash"][res["resume_step"] - 1]["loss"]
        post = res["post_crash"][0]["loss"]
        # resumed loss continues from the checkpoint region, not from init
        init_loss = res["pre_crash"][0]["loss"]
        assert post < init_loss - 0.2
        assert abs(post - pre) < abs(post - init_loss)

    def test_deterministic_restart_same_curve(self, tmp_path):
        """Determinism: two fresh trainers produce identical first steps."""
        a, b = _trainer(), _trainer()
        a.run(3, log_every=0)
        b.run(3, log_every=0)
        assert [h["loss"] for h in a.history] == [h["loss"] for h in b.history]


class TestServing:
    def test_engine_matches_reference(self):
        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = Engine(cfg, params, ServeConfig(max_seq=64, slots=3))
        prompts = [[1, 2, 3], [9, 8, 7, 6], [4, 4], [5, 1, 2, 3, 4]]
        reqs = [eng.submit(p, max_new=5) for p in prompts]
        eng.run_until_done()

        for r, p in zip(reqs, prompts):
            toks = list(p)
            ref = []
            for _ in range(5):
                lg = forward(params, cfg, {"tokens": jnp.asarray(toks)[None]},
                             mode="train")
                t = int(jnp.argmax(lg[0, -1]))
                ref.append(t)
                toks.append(t)
            assert r.out == ref, (r.out, ref)

    def test_slot_reuse(self):
        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = Engine(cfg, params, ServeConfig(max_seq=64, slots=2))
        reqs = [eng.submit([i + 1], max_new=3) for i in range(5)]
        eng.run_until_done()
        assert all(r.done and len(r.out) == 3 for r in reqs)


class TestElastic:
    def test_dead_worker_detected(self):
        mon = HealthMonitor(4, heartbeat_timeout=10.0)
        for w in range(4):
            mon.heartbeat(w)
        mon.advance(5.0)
        for w in (0, 1, 2):
            mon.heartbeat(w)
        mon.advance(6.0)
        for w in (0, 1, 2):
            mon.heartbeat(w)
        v = mon.check()
        assert v["dead"] == [3]
        assert mon.alive_workers() == [0, 1, 2]

    def test_straggler_detected(self):
        mon = HealthMonitor(4, straggler_factor=2.0)
        for step in range(8):
            for w in range(4):
                mon.record_step(step, 1.0 if w != 2 else 5.0, worker=w)
        v = mon.check()
        assert v["stragglers"] == [2]

    def test_straggler_judged_against_the_plan(self):
        # an imbalanced plan: worker 0 carries 4x the load and workers 1-3
        # idle in every other step; only a worker slower than its own plan
        # is a straggler
        mon = HealthMonitor(4, straggler_factor=2.0)
        planned = [[4.0, 1.0, 1.0, 1.0], [4.0, 0.0, 0.0, 0.0]]
        for rnd in range(4):
            for step, ts in enumerate(planned):
                for w in range(4):
                    slow = 3.0 if w == 3 else 1.0
                    mon.record_step(step, ts[w] * slow, worker=w,
                                    expected=ts[w])
        assert mon.check()["stragglers"] == [3]

    def test_remesh_resolves_schedule(self):
        from repro.core import random_dag
        dag = random_dag(20, 0.15, seed=2)
        mon = HealthMonitor(4, heartbeat_timeout=1.0)
        for w in range(4):
            mon.heartbeat(w)
        planner = ElasticPlanner(dag, heuristic="dsh")
        # kill worker 3
        mon.advance(2.0)
        for w in (0, 1, 2):
            mon.heartbeat(w)
        plan = planner.replan(mon)
        assert plan.action == "remesh"
        assert plan.workers == (0, 1, 2)
        assert plan.schedule.n_workers == 3
        from repro.core import validate
        validate(plan.schedule, dag)

    def test_all_dead_raises(self):
        mon = HealthMonitor(1, heartbeat_timeout=0.5)
        mon.advance(10.0)
        from repro.core import random_dag
        with pytest.raises(RuntimeError):
            ElasticPlanner(random_dag(5, 0.3)).replan(mon)

    def test_dead_worker_excluded_from_fleet_median(self):
        """Regression: a worker that stopped beating must not drag the
        straggler baseline with its stale (pathological) step times."""
        mon = HealthMonitor(4, heartbeat_timeout=10.0, straggler_factor=2.0)
        for step in range(6):
            for w in (0, 1):
                mon.record_step(step, 1.0, worker=w)
            mon.record_step(step, 2.5, worker=2)   # true straggler
            mon.record_step(step, 25.0, worker=3)  # wedged, then dies
        mon.advance(20.0)
        for step in range(6, 8):
            for w in (0, 1):
                mon.record_step(step, 1.0, worker=w)
            mon.record_step(step, 2.5, worker=2)
        v = mon.check()
        assert v["dead"] == [3]
        # with worker 3's stale 25.0s in the median the fleet baseline was
        # 1.75 and worker 2 (2.5 < 2 x 1.75) slipped through undetected
        assert v["stragglers"] == [2]

    def test_straggler_detected_at_zero_median(self):
        """Regression: a fleet median of exactly 0.0 (quantized timers)
        previously disabled straggler detection entirely."""
        mon = HealthMonitor(4, straggler_factor=2.0)
        for step in range(6):
            for w in (0, 1, 2):
                mon.record_step(step, 0.0, worker=w)
            mon.record_step(step, 1.0, worker=3)
        v = mon.check()
        assert v["stragglers"] == [3]

    def test_record_step_attributes_step(self):
        """Regression: record_step used to drop its ``step`` argument —
        overruns could not be attributed to a superstep bound."""
        mon = HealthMonitor(2, window=4)
        for s, dt in [(0, 1.0), (1, 2.0), (7, 3.0)]:
            mon.record_step(s, dt, worker=1)
        assert mon.workers[1].timings == [(0, 1.0), (1, 2.0), (7, 3.0)]
        assert mon.workers[1].step_times == [1.0, 2.0, 3.0]
        for s in range(10, 16):  # rolling window caps both views
            mon.record_step(s, 1.0, worker=1)
        assert len(mon.workers[1].timings) == 4
        assert mon.workers[1].timings[-1] == (15, 1.0)

    def test_deadline_verdict_from_certificate(self):
        from repro.codegen import WCETCertificate
        cert = WCETCertificate(compute_bounds=(1.0, 1.0),
                               comm_bounds=(0.0, 0.0))
        mon = HealthMonitor(2)
        mon.record_step(0, 0.5, worker=0)   # within bound
        mon.record_step(1, 5.0, worker=1)   # blows superstep 1's budget
        v = mon.check(certificate=cert)
        assert v["deadline"] == [1] and v["dead"] == []
        # generous slack absorbs the overrun; no certificate, no verdict
        assert mon.check(certificate=cert, slack=10.0)["deadline"] == []
        assert "deadline" not in mon.check()

    def test_deadline_overrun_triggers_replan(self):
        from repro.codegen import WCETCertificate
        from repro.core import random_dag, validate
        cert = WCETCertificate(compute_bounds=(1.0,), comm_bounds=(0.0,))
        dag = random_dag(20, 0.15, seed=5)
        mon = HealthMonitor(4, heartbeat_timeout=100.0)
        for w in range(4):
            mon.record_step(0, 4.0 if w == 2 else 3.0, worker=w)
        plan = ElasticPlanner(dag).replan(mon, certificate=cert)
        # fleet intact (nobody dead, nobody a 2x straggler) yet observed
        # supersteps break the certificate: re-solve rather than coast
        assert plan.action == "deadline_replan"
        assert plan.schedule.n_workers == 4
        validate(plan.schedule, dag)

    def test_sliced_replan_ships_plan_and_certificate(self):
        from repro.core.costmodel import KEYSTONE_CPU
        from repro.models.cnn import lenet5
        from repro.models.slicing import slice_model, uniform_factors
        model = lenet5()
        sliced = slice_model(model, uniform_factors(model, 4))
        sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        mon = HealthMonitor(4, heartbeat_timeout=1.0)
        for w in range(4):
            mon.heartbeat(w)
        mon.advance(2.0)
        for w in (0, 1, 2):
            mon.heartbeat(w)
        planner = ElasticPlanner(sdag, model=sliced, hw=KEYSTONE_CPU)
        plan = planner.replan(mon)
        assert plan.action == "remesh" and plan.workers == (0, 1, 2)
        assert plan.plan is not None and plan.plan.n_workers == 3
        assert plan.certificate is not None
        assert plan.certificate.n_steps == len(plan.plan.steps)
        assert plan.certificate.total >= plan.plan.makespan


class TestEngineRegression:
    def test_finished_at_prefill_emits_one_token(self):
        """Regression: a ``max_new=1`` request got its token at admit time
        but was parked in a slot, decoded one extra token (``len(out) ==
        2``), and released a tick later.  It must finish at admit with
        exactly one token and never occupy a slot."""
        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = Engine(cfg, params, ServeConfig(max_seq=64, slots=2))
        r1 = eng.submit([1, 2, 3], max_new=1)
        r3 = eng.submit([9, 8, 7], max_new=3)
        eng.tick()
        assert r1.done and len(r1.out) == 1
        # the prefill token is the argmax the reference forward produces
        lg = forward(params, cfg, {"tokens": jnp.asarray([[1, 2, 3]])},
                     mode="train")
        assert r1.out == [int(jnp.argmax(lg[0, -1]))]
        # the one-token request never held a slot; the other one does
        assert [req is r3 for req in eng.slot_req] == [True, False]
        eng.run_until_done()
        assert r3.done and len(r3.out) == 3

    def test_monitor_check_is_stable_under_repetition(self):
        """Regression: the first ``check()`` flipped ``w.alive`` and a
        second call returned an empty ``dead`` list — any caller running
        after ``ElasticPlanner.replan`` saw a clean fleet."""
        mon = HealthMonitor(3, heartbeat_timeout=5.0)
        for w in range(3):
            mon.heartbeat(w)
        mon.advance(6.0)
        mon.heartbeat(0)
        mon.heartbeat(1)
        v1 = mon.check()
        v2 = mon.check()
        assert v1["dead"] == [2] and v2["dead"] == [2]
        # read-only verdict: nothing committed, a later commit still lands
        mon2 = HealthMonitor(3, heartbeat_timeout=5.0)
        for w in range(3):
            mon2.heartbeat(w)
        mon2.advance(6.0)
        mon2.heartbeat(0)
        mon2.heartbeat(1)
        v = mon2.check(commit=False)
        assert v["dead"] == [2] and mon2.workers[2].alive
        assert mon2.check()["dead"] == [2]
        assert not mon2.workers[2].alive

    def test_per_worker_timing_source_detects_straggler(self):
        """Regression: ``Engine.tick`` recorded the whole-tick wall time
        against worker 0, so the engine path could never single out a
        straggler.  A ``timing_source`` feeds each worker its own time."""
        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        mon = HealthMonitor(3, heartbeat_timeout=1e9, straggler_factor=2.0)
        eng = Engine(cfg, params, ServeConfig(max_seq=64, slots=2),
                     monitor=mon, check_every=1,
                     timing_source=lambda: [(0, 1.0), (1, 1.0), (2, 5.0)])
        r = eng.submit([1, 2], max_new=3)
        eng.run_until_done()
        assert r.done
        assert mon.workers[2].step_times and mon.workers[0].step_times
        assert eng.last_verdict["stragglers"] == [2]
        assert eng.degraded

    def test_published_replan_restores_full_admission(self):
        """Degraded-mode recovery: once the planner publishes a replan for
        a death, the acknowledged death stops counting and a clean verdict
        restores full (multi-slot) admission."""
        from repro.core import random_dag
        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        mon = HealthMonitor(2, heartbeat_timeout=5.0)
        planner = ElasticPlanner(random_dag(12, 0.2, seed=1))
        eng = Engine(cfg, params, ServeConfig(max_seq=64, slots=3),
                     monitor=mon, planner=planner, check_every=1)
        mon.heartbeat(0)
        mon.heartbeat(1)
        mon.advance(6.0)
        mon.heartbeat(0)
        reqs = [eng.submit([i + 1], max_new=4) for i in range(3)]
        eng.tick()
        # death detected: degraded, replan published, one slot admitted
        assert eng.degraded
        assert eng.elastic_plan is not None
        assert eng.elastic_plan.action == "remesh"
        assert eng.elastic_plan.workers == (0,)
        assert sum(r is not None for r in eng.slot_req) == 1
        eng.tick()
        # the published replan acknowledged the death: clean verdict,
        # full admission resumes (every remaining request gets a slot)
        assert not eng.degraded
        assert sum(r is not None for r in eng.slot_req) == 3
        eng.run_until_done()
        assert all(r.done and len(r.out) == 4 for r in reqs)


class TestEngineDegradation:
    def test_unhealthy_fleet_flips_degraded_and_throttles_admission(self):
        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        mon = HealthMonitor(2, heartbeat_timeout=5.0)
        eng = Engine(cfg, params, ServeConfig(max_seq=64, slots=3),
                     monitor=mon, check_every=1)
        # worker 1 stops beating; worker 0 stays healthy
        mon.heartbeat(0)
        mon.heartbeat(1)
        mon.advance(6.0)
        mon.heartbeat(0)
        reqs = [eng.submit([i + 1], max_new=3) for i in range(3)]
        assert not eng.degraded
        eng.tick()  # health check fires first, then admission
        assert eng.degraded
        assert eng.last_verdict["dead"] == [1]
        # degraded admission: one new slot per tick instead of the full pool
        assert sum(r is not None for r in eng.slot_req) == 1
        eng.run_until_done()
        assert all(r.done and len(r.out) == 3 for r in reqs)
