"""Codegen: plan construction, python interpreter, pseudo-C, shard_map MPMD
executor (subprocess with placeholder devices)."""
import jax
import jax.numpy as jnp
import pytest

from repro.codegen import (
    ExecutionPlan,
    Superstep,
    Transfer,
    build_plan,
    coalesce_transfer_steps,
    interpret_plan,
    plan_liveness,
    render_pseudo_c,
)
from repro.core import dsh, ish, random_dag, validate
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import inception_net, lenet5, lenet5_branchy, run_sequential

KEY = jax.random.PRNGKey(0)


def _models():
    return [(lenet5(28), 28), (lenet5_branchy(28), 28), (inception_net(64), 64)]


class TestPlan:
    @pytest.mark.parametrize("heur", [ish, dsh])
    @pytest.mark.parametrize("m", [2, 4])
    def test_plan_covers_schedule(self, heur, m):
        model = inception_net(64)
        dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        s = heur(dag, m)
        plan = build_plan(s, dag)
        # every node computed at least once somewhere
        computed = {n for st in plan.steps for seg in st.compute for n in seg}
        assert computed == set(dag.nodes)
        # transfers only between distinct workers
        for st in plan.steps:
            for t in st.transfers:
                assert t.src != t.dst

    def test_comm_bytes_accounting(self):
        model = inception_net(64)
        dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        plan = build_plan(dsh(dag, 4), dag)
        out_bytes = {l.name: l.out_bytes() for l in model.layers}
        assert plan.comm_bytes(out_bytes) >= 0


class TestInterpreter:
    @pytest.mark.parametrize("heur", [ish, dsh])
    def test_matches_sequential(self, heur):
        for model, hw in _models():
            params = model.init_params(KEY)
            x = jax.random.normal(KEY, (2, hw, hw, model.layers[0].out_shape[-1]))
            ref = run_sequential(model, params, x)
            dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
            for m in (2, 4):
                s = heur(dag, m)
                validate(s, dag)
                y = interpret_plan(build_plan(s, dag), model, params, x)
                assert float(jnp.abs(y - ref).max()) < 1e-4

    def test_random_dag_plans_execute(self):
        """Property-ish: plans from random schedules are executable (no
        deadlock, full coverage)."""
        for seed in range(8):
            dag = random_dag(15, 0.2, seed=seed)
            s = dsh(dag, 3)
            plan = build_plan(s, dag)
            assert plan.n_workers == 3
            computed = {n for st in plan.steps for seg in st.compute for n in seg}
            assert computed == set(dag.nodes)


class TestLivenessAndCoalescing:
    def test_transfer_only_first_round_births_payload(self):
        """Regression: a node whose first plan appearance is as a transfer
        payload must be born at its producing superstep — previously its
        death defaulted against 0 with no birth at all, so the executor
        never materialized the register."""
        model = lenet5(28)
        plan = ExecutionPlan(
            n_workers=2,
            steps=(
                Superstep(compute=((), ()),
                          transfers=(Transfer("input", 0, 1),)),
                Superstep(compute=(("input",), ()), transfers=()),
            ),
            makespan=0.0, sink="input", sink_worker=0,
        )
        birth, death, live = plan_liveness(plan, model)
        assert birth["input"] == 0
        assert death["input"] == len(plan.steps)  # sink survives the plan
        assert "input" in live[0]
        assert all(death[b] >= birth[b] for b in birth)

    def test_coalesce_merges_transfer_only_steps(self):
        plan = ExecutionPlan(
            n_workers=2,
            steps=(
                Superstep(compute=(("input",), ()),
                          transfers=(Transfer("input", 0, 1),)),
                Superstep(compute=((), ()),
                          transfers=(Transfer("conv1", 0, 1),)),
                Superstep(compute=((), ()),
                          transfers=(Transfer("pool1", 0, 1),)),
                Superstep(compute=((), ("conv2",)), transfers=()),
            ),
            makespan=0.0, sink="conv2", sink_worker=1,
        )
        co = coalesce_transfer_steps(plan)
        assert len(co.steps) == 2
        assert len(co.steps[0].transfers) == 3
        assert co.n_transfers == plan.n_transfers
        # idempotent and identity on plans with nothing to merge
        assert coalesce_transfer_steps(co) is co

    def test_coalesce_keeps_unsafe_relays_separate(self):
        """A transfer whose source only *received* the value in the previous
        round must not fold into that round (the fused payload would read
        the relay's pre-round register)."""
        plan = ExecutionPlan(
            n_workers=3,
            steps=(
                Superstep(compute=(("input",), (), ()),
                          transfers=(Transfer("input", 0, 1),)),
                Superstep(compute=((), (), ()),
                          transfers=(Transfer("input", 1, 2),)),
            ),
            makespan=0.0, sink="input", sink_worker=0,
        )
        assert len(coalesce_transfer_steps(plan).steps) == 2

    def test_plan_suppliers_are_computers(self):
        """build_plan only ships from workers that computed the value —
        a receive-then-forward chain would break windowed payloads and
        coalesced fused rounds."""
        for seed in range(6):
            dag = random_dag(40, 0.2, seed=seed)
            plan = build_plan(dsh(dag, 4), dag)
            computed = set()
            for step in plan.steps:
                for w, seg in enumerate(step.compute):
                    computed.update((n, w) for n in seg)
                for t in step.transfers:
                    assert (t.node, t.src) in computed

    def test_coalesced_plan_interprets_identically(self):
        model = inception_net(64)
        params = model.init_params(KEY)
        x = jax.random.normal(KEY, (2, 64, 64, 3))
        dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        for lookahead in (True, False):
            plan = build_plan(dsh(dag, 4), dag, lookahead=lookahead)
            ref = interpret_plan(plan, model, params, x)
            y = interpret_plan(coalesce_transfer_steps(plan), model, params, x)
            assert float(jnp.abs(y - ref).max()) == 0.0


    def test_executor_coalesces_the_plan_it_is_given(self, subproc):
        """The executor coalesces transfer-only supersteps itself, and
        coalescing is idempotent: a plan with one superstep's transfers
        split off into a transfer-only superstep, that plan coalesced (as
        ``Frontend`` serves it), and the original all build the same
        executor — lowered program, carry width, segments and stats."""
        out = subproc("""
import dataclasses
import jax, numpy as np
from repro.codegen import (
    Superstep, build_plan, build_mpmd_executor, coalesce_transfer_steps,
)
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import lenet5
from repro.models.slicing import slice_model, uniform_factors

model = lenet5(28)
sliced = slice_model(model, uniform_factors(model, 4))
sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
plan = build_plan(dsh(sdag, 4), sdag)
assert coalesce_transfer_steps(plan) is plan
i = next(i for i, s in enumerate(plan.steps) if len(s.transfers) > 1)
st = plan.steps[i]
half = len(st.transfers) // 2
split = dataclasses.replace(plan, steps=(
    *plan.steps[:i],
    Superstep(compute=st.compute, transfers=st.transfers[:half]),
    Superstep(compute=((),) * 4, transfers=st.transfers[half:]),
    *plan.steps[i + 1:],
))
served = coalesce_transfer_steps(split)
assert len(split.steps) == len(plan.steps) + 1
assert served.steps == plan.steps
params = model.init_params(jax.random.PRNGKey(0))
mesh = jax.make_mesh((4,), ("workers",))
x = np.zeros((1, 28, 28, 1), np.float32)
built = [build_mpmd_executor(p, sliced, params, mesh, batch=1,
                             checkpoint=True)
         for p in (plan, split, served)]
texts = {f.lower(x).as_text() for f in built}
assert len(texts) == 1
for f in built[1:]:
    assert f.width == built[0].width
    assert f.segment_spans == built[0].segment_spans
    assert f.checkpoint_steps == built[0].checkpoint_steps
    assert f.segment_stats == built[0].segment_stats
print("COALESCED_BUILD_OK")
""", devices=4)
        assert "COALESCED_BUILD_OK" in out


class TestRender:
    def test_pseudo_c_contains_protocol(self):
        model = inception_net(64)
        dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        plan = build_plan(dsh(dag, 4), dag)
        txt = render_pseudo_c(plan)
        assert "INFERENCE_0" in txt and "INFERENCE_3" in txt
        if plan.n_transfers:
            assert "Writing" in txt and "Reading" in txt
            assert "flag_" in txt and "comm_" in txt


class TestShardMapExecutor:
    def test_mpmd_matches_sequential_subprocess(self, subproc):
        out = subproc("""
import jax, jax.numpy as jnp
from repro.models.cnn import inception_net, run_sequential
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.codegen import build_plan, build_mpmd_executor
key = jax.random.PRNGKey(0)
model = inception_net(64)
params = model.init_params(key)
x = jax.random.normal(key, (2, 64, 64, 3))
ref = run_sequential(model, params, x)
dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
for m in (2, 4):
    plan = build_plan(dsh(dag, m), dag)
    mesh = jax.make_mesh((m,), ("workers",))
    f = build_mpmd_executor(plan, model, params, mesh, batch=2)
    err = float(jnp.abs(f(x) - ref).max())
    assert err < 1e-4, (m, err)
print("MPMD_OK")
""", devices=4)
        assert "MPMD_OK" in out

    @pytest.mark.parametrize("heur", ["ish", "dsh"])
    def test_sliced_ish_and_dsh_plans_match_interpreter_subprocess(
        self, subproc, heur
    ):
        """ISH plans (no duplicated tasks) as well as DSH plans (duplicated
        tasks, fewer transfers) of a channel-sliced inception net through
        the executor at m = 2 and 4, against ``interpret_plan`` and the
        sequential reference."""
        out = subproc(f"""
import jax, jax.numpy as jnp
from repro.models.cnn import inception_net, run_sequential
from repro.models.slicing import slice_model, uniform_factors
from repro.core import {heur}
from repro.core.costmodel import KEYSTONE_CPU
from repro.codegen import build_plan, build_mpmd_executor, interpret_plan
key = jax.random.PRNGKey(0)
model = inception_net(64)
params = model.init_params(key)
x = jax.random.normal(key, (2, 64, 64, 3))
ref = run_sequential(model, params, x)
sliced = slice_model(model, uniform_factors(model, 2))
sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
for m in (2, 4):
    plan = build_plan({heur}(sdag, m), sdag)
    assert plan.n_transfers, m
    mesh = jax.make_mesh((m,), ("workers",))
    f = build_mpmd_executor(plan, sliced, params, mesh, batch=2)
    y = f(x)
    yi = interpret_plan(plan, sliced, params, x)
    assert float(jnp.abs(y - yi).max()) < 1e-5, m
    assert float(jnp.abs(y - ref).max()) < 1e-4, m
print("HEUR_MPMD_OK")
""", devices=4)
        assert "HEUR_MPMD_OK" in out


_PARAM_DIGEST = """
import hashlib, jax, numpy as np
from repro.models.cnn import inception_net, lenet5
h = hashlib.sha256()
for model in (lenet5(), inception_net(64)):
    params = model.init_params(jax.random.PRNGKey(0))
    for name in sorted(params):
        for k in sorted(params[name]):
            h.update(np.asarray(params[name][k]).tobytes())
print(h.hexdigest())
"""


def test_params_independent_of_hash_seed(subproc, monkeypatch):
    """A run and its reference in two processes see one model: parameters
    must not depend on ``PYTHONHASHSEED``."""
    digests = set()
    for seed in ("1", "2"):
        monkeypatch.setenv("PYTHONHASHSEED", seed)
        digests.add(subproc(_PARAM_DIGEST).strip())
    assert len(digests) == 1
