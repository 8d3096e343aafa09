"""Fast-path equivalence: heap-driven scheduler vs the reference driver,
cursor-based plan builder, liveness-packed executor.

The fast path is required to be *semantics-preserving*: identical instance
placements (hence identical makespans) to the original full-rescan driver,
executor outputs equal to the interpreter's, and strictly less executor
overhead (register live-set size)."""
import jax
import jax.numpy as jnp
import pytest

from repro.codegen import build_plan, interpret_plan, plan_liveness
from repro.core import Schedule, dsh, ish, random_dag, validate
from repro.core.costmodel import KEYSTONE_CPU
from repro.core.list_scheduling import list_schedule, list_schedule_reference
from repro.core.schedule import single_worker_schedule
from repro.models.cnn import inception_net, lenet5_branchy, run_sequential

KEY = jax.random.PRNGKey(0)


class TestSchedulerEquivalence:
    """Property: the heap-driven driver reproduces the reference exactly."""

    @pytest.mark.parametrize("duplicate", [False, True], ids=["ish", "dsh"])
    def test_matches_reference_on_random_dags(self, duplicate):
        checked = 0
        for seed in range(22):
            n = 8 + 3 * seed          # 8 .. 71 nodes
            m = (2, 3, 4, 8)[seed % 4]
            dens = (0.08, 0.15, 0.30)[seed % 3]
            dag = random_dag(n, dens, seed=seed)
            fast = list_schedule(dag, m, duplicate=duplicate)
            ref = list_schedule_reference(dag, m, duplicate=duplicate)
            validate(fast, dag)
            # instance-for-instance identical, not just equal makespans
            assert fast.instances == ref.instances, (seed, n, m)
            assert fast.makespan(dag) == pytest.approx(ref.makespan(dag))
            checked += 1
        assert checked >= 20

    def test_matches_reference_without_insertion(self):
        for seed in range(6):
            dag = random_dag(20, 0.2, seed=seed)
            fast = list_schedule(dag, 3, insertion=False)
            ref = list_schedule_reference(dag, 3, insertion=False)
            assert fast.instances == ref.instances

    def test_matches_reference_on_cnn_dags(self):
        for model in (inception_net(64), lenet5_branchy(28)):
            dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
            for m in (2, 4):
                for dup in (False, True):
                    fast = list_schedule(dag, m, duplicate=dup)
                    ref = list_schedule_reference(dag, m, duplicate=dup)
                    assert fast.instances == ref.instances


class TestGraphCaches:
    def test_cached_adjacency_consistent_with_edges(self):
        dag = random_dag(60, 0.15, seed=3)
        pm, cm = dag.parent_map(), dag.child_map()
        for (u, v) in dag.edges:
            assert u in pm[v] and v in cm[u]
        # memoized: same object across calls
        assert dag.parent_map() is pm
        assert dag.topological_order() is dag.topological_order()
        assert sum(dag.indegrees().values()) == len(dag.edges)

    def test_indegrees_copy_safe(self):
        dag = random_dag(10, 0.2, seed=0)
        d = dag.indegrees()
        d[dag.nodes[0]] = 99
        assert dag.indegrees()[dag.nodes[0]] != 99


class TestEarliestAvailability:
    def test_availability_matches_data_ready(self):
        dag = random_dag(25, 0.2, seed=7)
        s = dsh(dag, 3)
        for v in dag.nodes:
            for w in range(3):
                expect = 0.0
                for u in dag.parents(v):
                    expect = max(expect, s.earliest_availability(dag, u, w, v))
                assert s.data_ready(dag, v, w) == pytest.approx(expect)

    def test_local_instance_beats_remote(self):
        dag = random_dag(15, 0.2, seed=1)
        sched = single_worker_schedule(dag)
        v = dag.nodes[-1]
        ps = dag.parents(v)
        if ps:
            u = ps[0]
            local = sched.earliest_availability(dag, u, 0, v)
            remote = sched.earliest_availability(dag, u, 1, v)
            assert remote == pytest.approx(local + dag.w[(u, v)])


class TestPlanBuilderFast:
    def test_build_plan_500_node_dag(self):
        """Dedicated satellite check: the cursor-based builder digests a
        500-node schedule quickly and covers every node."""
        dag = random_dag(500, 0.05, seed=11)
        s = list_schedule(dag, 4)
        plan = build_plan(s, dag)
        computed = {n for st in plan.steps for seg in st.compute for n in seg}
        assert computed == set(dag.nodes)
        for st in plan.steps:
            for t in st.transfers:
                assert t.src != t.dst

    def test_plan_identical_to_seed_semantics(self):
        """The cursor rewrite must not change the emitted supersteps: the
        supplier of every transfer is still the earliest-finishing available
        instance and compute prefixes are maximal."""
        for seed in range(6):
            dag = random_dag(30, 0.15, seed=seed)
            s = dsh(dag, 3)
            plan = build_plan(s, dag)
            # simulate availability forward; every compute node's parents
            # must be locally available when its segment runs
            have = set()
            for st in plan.steps:
                for w, seg in enumerate(st.compute):
                    for nd in seg:
                        for u in dag.parents(nd):
                            assert (u, w) in have, (seed, nd, u, w)
                        have.add((nd, w))
                for t in st.transfers:
                    assert (t.node, t.src) in have
                    have.add((t.node, t.dst))


class TestExecutorLiveness:
    def test_live_sets_strictly_smaller_than_register_file(self):
        """Acceptance: on the schedule_cnn example model the per-superstep
        live set never reaches the full layer count."""
        model = inception_net(64)
        dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        for m in (2, 4):
            plan = build_plan(dsh(dag, m), dag)
            birth, death, live_sets = plan_liveness(plan, model)
            assert max(len(s) for s in live_sets) < len(model.layers)
            # sink lives past the last step; every birth precedes its death
            assert death[plan.sink] == len(plan.steps)
            for b in birth:
                assert birth[b] <= death[b]

    def test_liveness_covers_all_reads(self):
        model = lenet5_branchy(28)
        dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        plan = build_plan(ish(dag, 2), dag)
        birth, death, live_sets = plan_liveness(plan, model)
        for i, step in enumerate(plan.steps):
            for seg in step.compute:
                for name in seg:
                    spec = model.spec(name)
                    if spec.op != "input":
                        for p in spec.inputs:
                            assert birth[p] <= i <= death[p]
            for t in step.transfers:
                assert birth[t.node] <= i <= death[t.node]


class TestExecutorFusion:
    def test_interpreter_matches_executor_all_modes(self, subproc):
        """interpret_plan matches build_mpmd_executor in every mode the
        executor has: write-once and streaming staging, with and without
        checkpoint snapshots."""
        out = subproc("""
import itertools
import jax, jax.numpy as jnp
from repro.models.cnn import lenet5_branchy
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.codegen import build_plan, build_mpmd_executor, interpret_plan

key = jax.random.PRNGKey(1)
model = lenet5_branchy(28)
params = model.init_params(key)
x = jax.random.normal(key, (2, 28, 28, 1))
dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
plan = build_plan(dsh(dag, 4), dag)
y_interp = interpret_plan(plan, model, params, x)
mesh = jax.make_mesh((4,), ("workers",))
for depth, ckpt in itertools.product((1, 2), (False, True)):
    f = build_mpmd_executor(plan, model, params, mesh, batch=2,
                            checkpoint=ckpt, buffer_depth=depth)
    y = f(x)[0] if ckpt else f(x)
    err = float(jnp.abs(y - y_interp).max())
    assert err < 1e-4, (depth, ckpt, err)
print("MODES_OK")
""", devices=4)
        assert "MODES_OK" in out
