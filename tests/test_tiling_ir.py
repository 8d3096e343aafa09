"""Nested tiling IR geometry: 2-D (cout × rows) grids end-to-end (ISSUE 4).

Property-style coverage of the :class:`~repro.models.slicing.Tiling` tree:

* **partition** — the leaf boxes of every tiling (1-D, 2-D grids, and
  composed seen-through concat tilings with mixed-axis branches) exactly
  partition the producer tensor: disjoint, covering, in-bounds;
* **cost conservation** — grid slice FLOPs partition layer FLOPs exactly;
* **edge pricing** — direct-edge byte weights equal the consumer-window ∩
  producer-tile intersections recomputed independently from the leaf boxes
  of nested grids;
* **mixed-axis see-through** — spatial (row-tiled) inception branches
  compose through the channel concats: zero ``tile_concat`` glue on the
  dataflow path, none on the critical path (the PR 3 restriction lifted);
* **equivalence** — grid-sliced execution matches ``run_sequential``
  through the plan interpreter and the MPMD executor, and
  :func:`search_slice_factors` mappings stay numerically exact.
"""
import numpy as np

import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import dsh, ish, validate
from repro.core.costmodel import KEYSTONE_CPU, box_bytes
from repro.codegen import build_plan, interpret_plan
from repro.models.cnn import (
    _row_window,
    inception_net,
    lenet5,
    lenet5_branchy,
    run_sequential,
)
from repro.models.slicing import (
    Tiling,
    model_tilings,
    search_slice_factors,
    slice_model,
    slicing_summary,
    tiling_leaves,
    uniform_factors,
)

KEY = jax.random.PRNGKey(0)
WINDOW_OPS = ("conv", "maxpool", "avgpool")


def grid_factors(model, g, rest=4):
    """(cout, rows) grids on every conv/pool, ``rest`` tiles elsewhere."""
    return {
        l.name: (g if l.op in WINDOW_OPS and l.out_shape[0] > 1 else rest)
        for l in model.layers
        if l.op in (*WINDOW_OPS, "dense", "attn")
    }


def assert_partition(tiling, pshape):
    """Leaf boxes are in-bounds, pairwise disjoint, and cover pshape."""
    leaves = tiling_leaves(tiling, pshape)
    assert leaves
    vol = 0
    for name, box in leaves:
        assert len(box) == len(pshape), name
        for (lo, hi), d in zip(box, pshape):
            assert 0 <= lo < hi <= d, (name, box)
        vol += int(np.prod([hi - lo for lo, hi in box]))
    assert vol == int(np.prod(pshape)), "leaves do not cover the producer"
    for i, (n1, b1) in enumerate(leaves):
        for n2, b2 in leaves[i + 1:]:
            disjoint = any(
                hi1 <= lo2 or hi2 <= lo1
                for (lo1, hi1), (lo2, hi2) in zip(b1, b2)
            )
            assert disjoint, f"overlap: {n1} {b1} vs {n2} {b2}"


class TestPartition:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 5), st.integers(1, 5), st.booleans())
    def test_grid_boxes_partition_every_layer(self, pc, pr, spatial):
        """Every tiling a (pc, pr) grid request produces — grids, capped
        1-D degenerations, dense/attn row blocks — partitions its layer."""
        model = lenet5_branchy(28)
        factors = {
            l.name: ((pc, pr) if l.op in WINDOW_OPS else pc * pr)
            for l in model.layers
            if l.op in (*WINDOW_OPS, "dense", "attn")
        }
        tilings = model_tilings(model, factors)
        if pc * pr >= 2:
            assert tilings, "nothing sliced"
        for name, tiling in tilings.items():
            assert_partition(tiling, model.spec(name).out_shape)

    def test_composed_concat_tilings_partition(self):
        """Seen-through concat tilings — including mixed-axis branches —
        partition the concatenated output exactly."""
        model = inception_net(64)
        for factors in (
            uniform_factors(model, 8),
            uniform_factors(model, 8, spatial=True),
            grid_factors(model, (2, 4), rest=8),
            # mixed axes behind one concat: rows on two branches, channels
            # and a grid on the others
            {**uniform_factors(model, 8, spatial=True),
             "inception_1/conv_a": 4, "inception_1/conv_b2": (2, 2),
             "inception_2/conv_c2": 6},
        ):
            tilings = model_tilings(model, factors)
            for tag in ("inception_1/concat", "inception_2/concat"):
                assert tag in tilings, "concat not seen through"
                assert_partition(tilings[tag], model.spec(tag).out_shape)

    def test_grid_tiling_is_rows_of_channel_blocks(self):
        model = inception_net(64)
        tilings = model_tilings(model, {"conv_1": (2, 4)})
        t = tilings["conv_1"]
        out_h, _w, out_c = model.spec("conv_1").out_shape
        assert t.axis == 0 and t.dim == out_h and len(t.bounds) == 4
        for child in t.children:
            assert isinstance(child, Tiling)
            assert child.axis == -1 and child.dim == out_c
            assert len(child.bounds) == 2
        assert t.n_leaves() == 8


class TestCostConservation:
    @pytest.mark.parametrize("g", [(2, 2), (4, 2), (3, 3)])
    def test_grid_slice_flops_conserve_layer_flops(self, g):
        for model in (lenet5(28), inception_net(64)):
            sliced = slice_model(model, grid_factors(model, g))
            by_origin = {}
            for s in sliced.layers:
                if s.op.endswith("_slice"):
                    by_origin.setdefault(s.attrs["origin"], []).append(s)
            assert by_origin
            grid_seen = 0
            for origin, slices in by_origin.items():
                layer = model.spec(origin)
                lf = layer.cost().flops
                sf = sum(s.cost().flops for s in slices)
                assert sf == pytest.approx(lf, rel=1e-9), origin
                lt = layer.cost().time(KEYSTONE_CPU)
                stt = sum(s.cost().time(KEYSTONE_CPU) for s in slices)
                assert lt - 1e-12 <= stt <= lt * (1.0 + 0.2 * len(slices))
                grid_seen += any(
                    s.attrs["tile"][0] == "grid" for s in slices
                )
            assert grid_seen >= 2, "no 2-D grids in the lowering"


def _edge_bytes(dag, e, time_unit=1e-6):
    """Invert KEYSTONE comm_time to recover the bytes an edge was priced at."""
    return (dag.w[e] * time_unit - KEYSTONE_CPU.ici_latency) * KEYSTONE_CPU.ici_bw


def _consumer_window(l, pshape):
    """Recompute the producer window a slice consumer reads, from scratch."""
    box = [(0, d) for d in pshape]
    a = l.attrs
    if l.op in ("conv_slice", "pool_slice") and len(pshape) == 3:
        k = a["kernel"]
        s = a["stride"]
        ra, rb, _, _ = _row_window(a["r_lo"], a["r_hi"], a["in_shape"][0], k, s)
        box[0] = (ra, rb)
        if l.op == "pool_slice":
            box[-1] = (a["c_lo"], a["c_hi"])
    return tuple(box)


class TestDirectEdgePricing:
    @pytest.mark.parametrize("g", [(2, 2), (2, 4)])
    def test_grid_edge_bytes_match_leaf_box_intersections(self, g):
        """Every direct edge into a grid consumer is priced at exactly the
        consumer-window ∩ leaf-box intersection, where both the window and
        the leaf boxes (incl. through seen-through concats) are recomputed
        independently of the slicer's in_boxes."""
        model = inception_net(64)
        factors = grid_factors(model, g, rest=4)
        sliced = slice_model(model, factors)
        sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        tilings = model_tilings(model, factors)
        leaf_box = {}
        for pname, tiling in tilings.items():
            for name, box in tiling_leaves(tiling, model.spec(pname).out_shape):
                leaf_box.setdefault(name, {})[pname] = box
        checked = 0
        for l in sliced.layers:
            if not l.op.endswith("_slice") or "in_layout" not in l.attrs:
                continue
            porigs = model.spec(l.attrs["origin"]).inputs
            for pname in l.inputs:
                # which logical producer did this tile come from?
                cands = [
                    (po, leaf_box[pname][po])
                    for po in porigs
                    if po in leaf_box.get(pname, {})
                ]
                if not cands:
                    continue  # untiled pass-through input
                porig, box = cands[0]
                window = _consumer_window(l, model.spec(porig).out_shape)
                inter = tuple(
                    (max(a, lo), min(b, hi))
                    for (a, b), (lo, hi) in zip(window, box)
                )
                expect = box_bytes(inter)
                got = _edge_bytes(sdag, (pname, l.name))
                assert got == pytest.approx(expect, rel=1e-6), (l.name, pname)
                checked += 1
        assert checked > 100


class TestMixedAxisSeeThrough:
    def test_spatial_inception_has_zero_glue_on_dataflow_path(self):
        """Acceptance: row-tiled branches behind the channel concats compose
        — no module concat survives, no tile_concat feeds a slice consumer,
        and the scheduled critical path carries only boundary glue."""
        model = inception_net(64)
        for factors in (
            uniform_factors(model, 8, spatial=True),
            grid_factors(model, (2, 4), rest=8),
        ):
            sliced = slice_model(model, factors)
            sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
            assert "inception_1/concat" not in set(sdag.nodes)
            assert "inception_2/concat" not in set(sdag.nodes)
            glue = {l.name for l in sliced.layers if l.op == "tile_concat"}
            assert glue == {"avgpool", "gemm"}, glue
            cm = sdag.child_map()
            for gl in glue:
                for c in cm[gl]:
                    assert not sliced.spec(c).op.endswith("_slice"), (gl, c)
            # walk the comm-inclusive critical path: no glue before the
            # flatten/output boundary
            lv = sdag.levels_with_comm()
            node = max(lv, key=lambda n: lv[n])
            while True:
                if node in glue:
                    assert node in ("avgpool", "gemm")
                cs = cm[node]
                if not cs:
                    break
                node = max(cs, key=lambda c: lv[c] + sdag.w[(node, c)])

    def test_summary_counts_grid_layers(self):
        model = inception_net(64)
        sliced = slice_model(model, grid_factors(model, (2, 2), rest=4))
        summary = slicing_summary(model, sliced)
        assert summary["grid_layers"] >= 10
        assert summary["glue_nodes"] == 2
        assert summary["direct_edges"] > summary["slice_tasks"]


class TestEquivalence:
    @pytest.mark.parametrize("g", [(2, 2), (2, 4), (4, 2)])
    @pytest.mark.parametrize("direct", [True, False])
    def test_grid_sequential_matches_unsliced(self, g, direct):
        for model in (lenet5(28), lenet5_branchy(28), inception_net(64)):
            params = model.init_params(KEY)
            x = jax.random.normal(KEY, (2, *model.layers[0].out_shape))
            ref = run_sequential(model, params, x)
            sliced = slice_model(model, grid_factors(model, g), direct=direct)
            y = run_sequential(sliced, params, x)
            assert float(jnp.abs(y - ref).max()) < 1e-4, (model.name, g)

    @pytest.mark.parametrize("heur", [ish, dsh])
    def test_grid_plans_match_sequential(self, heur):
        model = inception_net(64)
        params = model.init_params(KEY)
        x = jax.random.normal(KEY, (2, *model.layers[0].out_shape))
        ref = run_sequential(model, params, x)
        sliced = slice_model(model, grid_factors(model, (2, 2), rest=4))
        sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
        for m in (2, 4, 8):
            s = heur(sdag, m)
            validate(s, sdag)
            y = interpret_plan(build_plan(s, sdag), sliced, params, x)
            assert float(jnp.abs(y - ref).max()) < 1e-4, m

    def test_grid_mpmd_matches_sequential_subprocess(self, subproc):
        """2-D grid plans — windowed transfers over nested tilings —
        through the real shard_map executor."""
        out = subproc("""
import jax, jax.numpy as jnp
from repro.models.cnn import inception_net, lenet5_branchy, run_sequential
from repro.models.slicing import slice_model
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.codegen import build_plan, build_mpmd_executor
W = ("conv", "maxpool", "avgpool")
key = jax.random.PRNGKey(0)
for model, g in ((lenet5_branchy(28), (2, 2)), (inception_net(64), (2, 2))):
    factors = {l.name: (g if l.op in W and l.out_shape[0] > 1 else 2)
               for l in model.layers if l.op in (*W, "dense")}
    params = model.init_params(key)
    x = jax.random.normal(key, (2, *model.layers[0].out_shape))
    ref = run_sequential(model, params, x)
    sliced = slice_model(model, factors)
    sdag = sliced.to_dag(KEYSTONE_CPU, time_unit=1e-6)
    for m in (2, 4):
        plan = build_plan(dsh(sdag, m), sdag)
        mesh = jax.make_mesh((m,), ("workers",))
        f = build_mpmd_executor(plan, sliced, params, mesh, batch=2)
        err = float(jnp.abs(f(x) - ref).max())
        assert err < 1e-4, (model.name, m, err)
print("GRID_MPMD_OK")
""", devices=4)
        assert "GRID_MPMD_OK" in out

    def test_search_slice_factors_mapping_is_exact_and_deterministic(self):
        """The schedule-aware search returns a mapping slice_model executes
        bit-exactly, and the search is deterministic."""
        model = lenet5(28)
        f1 = search_slice_factors(model, KEYSTONE_CPU, m=4, rounds=1,
                                  seeds=(2,), time_unit=1e-6,
                                  candidates=(None, 2, (1, 2), (2, 2)))
        f2 = search_slice_factors(model, KEYSTONE_CPU, m=4, rounds=1,
                                  seeds=(2,), time_unit=1e-6,
                                  candidates=(None, 2, (1, 2), (2, 2)))
        assert f1 == f2
        params = model.init_params(KEY)
        x = jax.random.normal(KEY, (2, *model.layers[0].out_shape))
        ref = run_sequential(model, params, x)
        y = run_sequential(slice_model(model, f1), params, x)
        assert float(jnp.abs(y - ref).max()) < 1e-4
