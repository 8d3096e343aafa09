"""Static concurrency analyzer (codegen/analyze.py) + mutation oracle.

The analyzer's contract has two sides, and both are tested here:

* **soundness on good plans** — every plan the repo's pipelines build
  (lenet5, grid-sliced inception, all streaming buffer depths including
  the non-power-of-two 3) must verify hazard-free, with the sync report
  asserting minimality or quantifying removable sync;
* **sensitivity on broken plans** — every seeded mutation class in
  ``tests/mutations.py`` (dropped rounds/transfers/barriers, misrouted
  and doubled deliveries, aliased registers, frame-parity swaps, late
  retire copies, cohort mispadding, suppressed landings) must be caught.

Plus the integration seams: ``validate_plan(deep=True)`` raising
:class:`PlanHazardError` with coordinates, the content-fingerprint memo
keeping repeat validations at hash cost, and :class:`ElasticPlanner`
refusing to ship a hazardous degraded replan.
"""
import time

import pytest

from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.codegen.analyze import AnalysisReport, PlanHazardError, analyze_plan
from repro.codegen.plan import build_plan, coalesce_transfer_steps
from repro.codegen import validate as validate_mod
from repro.codegen.validate import PlanValidationError, validate_plan
from repro.models.cnn import inception_net, lenet5
from repro.models.slicing import slice_model, uniform_factors

from conftest import run_subprocess
from mutations import MUTATION_CLASSES, mutate


def _pipeline(model, factors, m):
    sliced = slice_model(model, factors)
    sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
    plan = coalesce_transfer_steps(build_plan(dsh(sdag, m), sdag))
    return sliced, sdag, plan


@pytest.fixture(scope="module")
def lenet_cfg():
    model = lenet5(28)
    return _pipeline(model, uniform_factors(model, 4), 4)


@pytest.fixture(scope="module")
def inception_cfg():
    """The headline config: grid-sliced inception(64) on 8 workers."""
    model = inception_net(64)
    base = uniform_factors(model, 8, spatial=True)
    factors = {k: ((2, 4) if v == (1, 8) else v) for k, v in base.items()}
    return _pipeline(model, factors, 8)


# --------------------------------------------------------------------------- #
# clean passes: good plans verify hazard-free at every depth
# --------------------------------------------------------------------------- #
def test_lenet_clean_all_depths(lenet_cfg):
    """Depth 3 rides along: the analyzer (like the generalized
    ``_check_staging``) must be depth-agnostic, not enumerate {1,2,4}."""
    sliced, sdag, plan = lenet_cfg
    rep = analyze_plan(plan, sdag, sliced, depths=(1, 2, 3, 4))
    assert rep.ok, rep.summary()
    assert set(rep.stats["per_depth"]) == {1, 2, 3, 4}
    assert rep.stats["cell_events"] > 0
    assert rep.segments, "per-segment report missing"
    assert all(row["hazards"] == 0 for row in rep.segments)


def test_headline_clean_depths_1_2_4(inception_cfg):
    """Acceptance: the headline grid plan is proved hazard-free at the
    streaming depths."""
    sliced, sdag, plan = inception_cfg
    rep = analyze_plan(plan, sdag, sliced, depths=(1, 2, 4))
    assert rep.ok, rep.summary()
    s = rep.summary()
    for prop in ("race-free", "donation-safe", "sync-sufficient",
                 "deterministic"):
        assert prop in s


def test_sync_report_minimal_or_quantified(lenet_cfg, inception_cfg):
    """Acceptance: the removable-sync report either quantifies a finding
    (deferrable rounds / unread payloads) or asserts minimality."""
    for sliced, sdag, plan in (lenet_cfg, inception_cfg):
        rep = analyze_plan(plan, sdag, sliced, depths=(1,))
        s = rep.sync
        assert s["transfers"] > 0 and s["comm_rounds"] > 0
        assert s["consumed_transfers"] <= s["transfers"]
        if s["verdict"].startswith("minimal"):
            assert s["deferrable_rounds"] == 0
            assert s["unread_transfers"] == 0
        else:
            assert s["deferrable_rounds"] > 0 or s["unread_transfers"] > 0
        # slack attribution covers every consumed payload
        assert 0 <= s["zero_slack_transfers"] <= s["consumed_transfers"]


def test_model_free_analysis_is_superstep_level(lenet_cfg):
    """Without a model the analyzer still runs the superstep-level HB
    verification (this is the conftest wrapper's path — numpy, no jax)."""
    _, sdag, plan = lenet_cfg
    rep = analyze_plan(plan, sdag)
    assert rep.ok
    assert rep.depths == ()
    assert rep.stats["cell_events"] == 0
    assert rep.stats["plan_events"] > 0


# --------------------------------------------------------------------------- #
# mutation oracle: every class must be caught
# --------------------------------------------------------------------------- #
def _analysis_depths(mut):
    # table tampers target the frame machinery — analyze at (>= min_depth)
    # streaming depth; plan-level mutations are visible at any depth
    return (max(mut.min_depth, 2),) if mut.tamper else (1, 2)


@pytest.mark.parametrize("cls", MUTATION_CLASSES)
def test_mutation_caught_lenet(lenet_cfg, cls):
    sliced, sdag, plan = lenet_cfg
    mut = mutate(cls, plan, sdag, sliced, seed=0)
    assert mut is not None, f"{cls}: lenet5 plan can't express the bug"
    rep = analyze_plan(mut.plan, sdag, sliced, depths=_analysis_depths(mut),
                       offsets=mut.offsets, tamper=mut.tamper)
    assert not rep.ok, f"{cls} NOT caught ({mut.detail})"
    # every hazard carries coordinates a human can act on
    h = rep.hazards[0]
    assert h.kind and h.detail
    assert str(h).startswith(f"[{h.kind}]")


@pytest.mark.parametrize("cls", MUTATION_CLASSES)
def test_mutation_caught_headline(inception_cfg, cls):
    """The oracle must hold on the config CI actually gates — the
    grid-sliced inception plan with its water-filled retire windows."""
    sliced, sdag, plan = inception_cfg
    mut = mutate(cls, plan, sdag, sliced, seed=0)
    assert mut is not None, f"{cls}: headline plan can't express the bug"
    rep = analyze_plan(mut.plan, sdag, sliced, depths=_analysis_depths(mut),
                       offsets=mut.offsets, tamper=mut.tamper)
    assert not rep.ok, f"{cls} NOT caught ({mut.detail})"


def test_mutation_raises_through_deep_validate(lenet_cfg):
    """The conftest/elastic seam, both layers: a plan-IR-expressible bug
    is refused by ``validate_plan(deep=True)`` (the structural layer
    catches it first — defense in depth, either layer refusing is a
    refusal), while a table-level bug the plan IR can't express raises
    :class:`PlanHazardError` from the analyzer — and PlanHazardError is a
    PlanValidationError subclass, so every caller's except clause covers
    both layers uniformly."""
    sliced, sdag, plan = lenet_cfg
    mut = mutate("drop_transfer", plan, sdag, sliced, seed=0)
    with pytest.raises(PlanValidationError):
        validate_plan(mut.plan, sdag, model=sliced, deep=True, cache=False)

    mut = mutate("mispad_cohort", plan, sdag, sliced, seed=0)
    with pytest.raises(PlanHazardError) as ei:
        analyze_plan(plan, sdag, sliced, depths=(2,), tamper=mut.tamper,
                     raise_on_hazard=True)
    assert isinstance(ei.value, PlanValidationError)
    assert isinstance(ei.value.report, AnalysisReport)
    assert ei.value.report.hazards


# --------------------------------------------------------------------------- #
# failure coordinates & the fingerprint memo
# --------------------------------------------------------------------------- #
def test_structural_failure_has_coordinates(lenet_cfg):
    """Satellite: structural failures name (superstep, worker) and quote
    the offending node/transfer, so the first line of the error is enough
    to find the bug in a plan dump."""
    sliced, sdag, plan = lenet_cfg
    mut = mutate("misroute_transfer", plan, sdag, sliced, seed=0)
    with pytest.raises(PlanValidationError) as ei:
        validate_plan(mut.plan, sdag, model=sliced, cache=False)
    msg = str(ei.value)
    assert msg.startswith("[superstep ")
    assert "worker" in msg
    assert "'" in msg, "node/transfer names must be quoted"


def test_hazard_messages_carry_plan_coordinates(lenet_cfg):
    """A superstep-level hazard names the step and the node; a cell-level
    hazard additionally pins (segment, tick, worker)."""
    sliced, sdag, plan = lenet_cfg
    mut = mutate("drop_transfer", plan, sdag, sliced, seed=0)
    rep = analyze_plan(mut.plan, sdag, sliced, depths=(1,))
    plan_level = [h for h in rep.hazards if h.step is not None]
    assert plan_level and all(h.node for h in plan_level)
    assert "step" in str(plan_level[0])

    mut = mutate("drop_round_fire", plan, sdag, sliced, seed=0)
    rep = analyze_plan(plan, sdag, sliced, depths=(2,), tamper=mut.tamper)
    cell_level = [h for h in rep.hazards if h.segment is not None]
    assert cell_level
    s = str(cell_level[0])
    assert "segment" in s and "tick" in s


def test_validation_memo_dedups_deep_analysis(lenet_cfg, monkeypatch):
    """Identical (plan, dag, model) revalidations must cost one hash, not
    one abstract interpretation — this is what keeps the conftest wrapper
    (deep=True on *every* built plan) off the tier-1 critical path."""
    import repro.codegen.analyze as analyze_mod

    sliced, sdag, plan = lenet_cfg
    calls = {"n": 0}
    real = analyze_mod.analyze_plan

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(analyze_mod, "analyze_plan", counting)
    validate_mod._MEMO.clear()
    validate_plan(plan, sdag, model=sliced, deep=True)
    assert calls["n"] == 1
    t0 = time.perf_counter()
    validate_plan(plan, sdag, model=sliced, deep=True)
    cached_s = time.perf_counter() - t0
    assert calls["n"] == 1, "memo miss: deep analysis re-ran"
    assert cached_s < 0.05, f"cached validation took {cached_s:.3f}s"


# --------------------------------------------------------------------------- #
# ElasticPlanner refuses hazardous degraded plans
# --------------------------------------------------------------------------- #
def test_elastic_planner_refuses_hazardous_replan(lenet_cfg, monkeypatch):
    """A degraded replan that comes out racy (simulated by routing the
    planner's build through the mutation oracle) must raise — a hazardous
    plan is an exception, never a deployed plan."""
    import repro.runtime.elastic as elastic_mod

    sliced, sdag, plan = lenet_cfg
    mut = mutate("drop_transfer", plan, sdag, sliced, seed=0)
    planner = elastic_mod.ElasticPlanner(sdag, model=sliced)
    sched = dsh(sdag, 4)

    monkeypatch.setattr(elastic_mod, "build_plan",
                        lambda s, d, *a, **kw: mut.plan)
    monkeypatch.setattr(elastic_mod, "coalesce_transfer_steps", lambda p: p)
    # deep=True refuses at whichever layer fires first (PlanHazardError is
    # a PlanValidationError, so this covers both)
    with pytest.raises(PlanValidationError):
        planner._finalize(list(range(4)), sched, "remesh")

    # and the same pipeline with the honest build ships a verified plan
    monkeypatch.undo()
    ep = planner._finalize(list(range(4)), sched, "remesh")
    assert ep.plan is not None


# --------------------------------------------------------------------------- #
# depth-3 regression: generalized staging depths end to end
# --------------------------------------------------------------------------- #
def test_depth3_validates_and_executes(lenet_cfg):
    """``_check_staging`` used to enumerate {1,2,4}; any depth >= 1 must
    now validate, analyze, and *execute* bit-identically (the executor
    run is the proof the generalization reaches the lowered scan)."""
    sliced, sdag, plan = lenet_cfg
    validate_plan(plan, sdag, model=sliced, staging_depths=(3,), cache=False)
    rep = analyze_plan(plan, sdag, sliced, depths=(3,))
    assert rep.ok, rep.summary()
    out = run_subprocess("""
import jax
from repro.codegen import build_plan
from repro.codegen.executor import build_mpmd_executor
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.models.cnn import lenet5
from repro.models.slicing import slice_model, uniform_factors

model = lenet5(28)
sliced = slice_model(model, uniform_factors(model, 4))
sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
plan = build_plan(dsh(sdag, 4), sdag)
key = jax.random.PRNGKey(0)
params = model.init_params(key)
x = jax.random.normal(key, (2, 28, 28, 1))
mesh = jax.make_mesh((4,), ("workers",))
ys = [build_mpmd_executor(plan, sliced, params, mesh, batch=2,
                          buffer_depth=d)(x)
      for d in (1, 3)]
assert bool((ys[0] == ys[1]).all())
print("DEPTH3_BITID_OK")
""", devices=4, timeout=900)
    assert "DEPTH3_BITID_OK" in out
