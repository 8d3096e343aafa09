"""The plan path's own tracing: named scopes in the compiled executor,
``serve.*`` profiler spans in ``Frontend``, and the set-up duration events
it reports through ``jax.monitoring``."""
import glob
import json
import os

import jax
import numpy as np
import pytest

SERVE_SPANS = ("serve.health", "serve.admit", "serve.dispatch",
               "serve.wait_snapshot", "serve.monitor", "serve.output",
               "serve.complete")
PLAN_EVENTS = ("/repro/plan/slice_search", "/repro/plan/schedule",
               "/repro/plan/build", "/repro/plan/validate",
               "/repro/plan/certificate")

# Compiles one executor twice, with its named scopes and with
# ``jax.named_scope`` made a no-op, and compares the compiled HLO with the
# metadata and the debug tables it indexes taken out.
_SCOPES = r"""
import contextlib, json, re, sys
import jax, numpy as np
from repro.codegen import build_plan
from repro.codegen.executor import build_mpmd_executor
from repro.codegen.plan import coalesce_transfer_steps
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU, TPU_V5E
from repro.models.cnn import inception_net, lenet5
from repro.models.slicing import search_slice_factors, slice_model, uniform_factors

case, m, depth = {case!r}, {m!r}, {depth!r}
if case == "inception32":
    model, hw = inception_net(32), TPU_V5E
    factors = search_slice_factors(model, hw, m=m)
else:  # channel slices priced for a CPU: rotating frames retire values
    model, hw = lenet5(28), KEYSTONE_CPU
    factors = uniform_factors(model, m)
params = model.init_params(jax.random.PRNGKey(0))
sliced = slice_model(model, factors)
dag = sliced.to_dag(hw, time_unit=1e-6)
plan = coalesce_transfer_steps(build_plan(dsh(dag, m), dag))
mesh = jax.make_mesh((m,), ("workers",))
x = np.zeros((1, *model.layers[0].out_shape), np.float32)

def compiled():
    f = build_mpmd_executor(plan, sliced, params, mesh, batch=1,
                            checkpoint=True, buffer_depth=depth)
    return f.lower(x).compile().as_text()

def program(text):
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    blocks = [b for b in text.split("\n\n") if b.split("\n", 1)[0] not in tables]
    return re.sub(r", metadata=\{{[^}}]*\}}", "", "\n\n".join(blocks))

scoped = compiled()
real = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
bare = compiled()
jax.named_scope = real
names = set(re.findall(r'op_name="([^"]*)"', scoped))
parts = sorted({{p for n in names for p in n.split("/")}})
print("RESULT:" + json.dumps({{"same": program(scoped) == program(bare),
                             "scoped_differs": scoped != bare,
                             "parts": parts}}))
"""


@pytest.mark.parametrize("case,m,depth,want", [
    ("inception32", 1, 1, {"seg0", "assemble", "params", "kernel", "land",
                           "output"}),
    ("inception32", 4, 1, {"seg0", "assemble", "params", "kernel", "land",
                           "comm", "checkpoint", "output"}),
    ("lenet5", 4, 2, {"seg0", "seg1", "assemble", "params", "kernel", "land",
                      "retire", "comm", "checkpoint", "output"}),
])
def test_named_scopes_leave_the_compiled_program_unchanged(subproc, case, m,
                                                           depth, want):
    out = subproc(_SCOPES.format(case=case, m=m, depth=depth), devices=4,
                  timeout=900)
    res = json.loads(next(l for l in out.splitlines()
                          if l.startswith("RESULT:"))[len("RESULT:"):])
    assert res["same"], "named scopes changed the compiled program"
    assert res["scoped_differs"], "no scope reached the op metadata"
    parts = set(res["parts"])
    assert want <= parts, sorted(want - parts)
    segs = {p for p in parts if p.startswith("seg")}
    assert all(p[3:].isdigit() for p in segs), segs


def _frontend(rows=1):
    from repro.core.costmodel import TPU_V5E
    from repro.models.cnn import lenet5
    from repro.models.slicing import search_slice_factors, slice_model
    from repro.serve import Frontend, FrontendConfig

    model = lenet5(28)
    params = model.init_params(jax.random.PRNGKey(0))
    sliced = slice_model(model, search_slice_factors(model, TPU_V5E, m=1,
                                                     rounds=1))
    dag = sliced.to_dag(TPU_V5E, time_unit=1e-6)
    fe = Frontend(sliced, params, dag, m=1, hw=TPU_V5E,
                  cfg=FrontendConfig(max_rows=rows), time_unit=1e-6)
    pool = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                        (4, *model.layers[0].out_shape)))
    return fe, pool


def _serve(fe, pool, rid, rows):
    from repro.serve.trace import TraceRequest

    r = fe.submit(TraceRequest(rid, fe.now, rows, rid % len(pool), np.inf),
                  pool)
    assert r.status == "queued"
    fe.step()
    assert r.status == "done"


@pytest.fixture
def repro_events():
    events = []

    def listen(event, duration, **attrs):
        if event.startswith("/repro/"):
            events.append((event, duration, attrs))

    jax.monitoring.register_event_duration_secs_listener(listen)
    yield events
    jax.monitoring.unregister_event_duration_listener(listen)


def test_frontend_reports_setup_and_executor_builds(repro_events):
    """Each set-up phase is reported once; each batch-size bucket's
    executor is built once, at the tick that first needed it."""
    fe, pool = _frontend(rows=2)
    names = [e for e, _, _ in repro_events]
    assert sorted(names) == sorted(PLAN_EVENTS)
    assert all(d >= 0 for _, d, _ in repro_events)
    fe.attach_executor(buckets=(1, 2))
    for rid, rows in enumerate((1, 2, 1, 2, 1)):
        _serve(fe, pool, rid, rows)
    builds = [a for e, _, a in repro_events
              if e == "/repro/serve/executor_build"]
    assert builds == [{"bucket": 1, "tick": 1}, {"bucket": 2, "tick": 2}]
    assert fe.exec_runs == 5


def test_serve_spans_carry_the_tick_and_the_request(tmp_path):
    """A profiler trace of served requests holds every ``serve.*`` span
    once per tick, tagged with the tick, and ``serve.submit`` tagged with
    the request id."""
    from jax.profiler import ProfileData

    fe, pool = _frontend()
    fe.attach_executor(buckets=(1,))
    _serve(fe, pool, 0, 1)  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for rid in (1, 2, 3):
            _serve(fe, pool, rid, 1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, dict(e.stats))
             for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith("serve.")]
    ticks = {}
    for name, stats in spans:
        if name != "serve.submit":
            ticks.setdefault(stats["tick"], []).append(name)
    assert sorted(ticks) == [2, 3, 4]
    assert all(sorted(v) == sorted(SERVE_SPANS) for v in ticks.values())
    rids = [stats["rid"] for name, stats in spans if name == "serve.submit"]
    assert sorted(rids) == [1, 2, 3]
