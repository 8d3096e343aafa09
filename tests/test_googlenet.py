"""The full GoogLeNet builder (Szegedy et al. 2015, Table 1) and the slice
search's counters, which say how large a plan the search priced."""
import jax
import pytest

from repro.core.costmodel import TPU_V5E
from repro.core.list_scheduling import dsh
from repro.models import slicing
from repro.models.cnn import googlenet, inception_net

# Table 1's output size of each inception module: (rows, channels)
TABLE1_MODULES = {
    "3a": (28, 256), "3b": (28, 480),
    "4a": (14, 512), "4b": (14, 512), "4c": (14, 512), "4d": (14, 528),
    "4e": (14, 832),
    "5a": (7, 832), "5b": (7, 1024),
}


def _n_params(model) -> int:
    n = 0
    for l in model.layers:
        a = l.attrs
        if l.op == "conv":
            n += a["kernel"] ** 2 * a["in_shape"][2] * a["features"] + a["features"]
        elif l.op == "dense":
            n += (a["in_features"] + 1) * a["features"]
    return n


def test_googlenet_follows_table1():
    model = googlenet()
    ops = [l.op for l in model.layers]
    assert ops.count("conv") == 57
    assert ops.count("maxpool") + ops.count("avgpool") == 14
    assert ops.count("concat") == 9
    assert _n_params(model) == 6_998_552
    for tag, (rows, channels) in TABLE1_MODULES.items():
        assert model.spec(f"inception_{tag}/concat").out_shape == (rows, rows, channels)
    assert model.spec("conv_2_reduce").attrs["kernel"] == 1
    fc = model.spec("fc")
    assert (fc.attrs["in_features"], fc.attrs["features"], fc.attrs["relu"]) == (1024, 1000, False)
    assert model.layers[-1].out_shape == (1000,)


@pytest.mark.parametrize("pool,size", [("maxpool_1", 56), ("maxpool_2", 28),
                                       ("maxpool_3", 14), ("maxpool_4", 7)])
def test_stride_two_pools_halve_as_in_table1(pool, size):
    spec = googlenet().spec(pool)
    assert (spec.attrs["kernel"], spec.attrs["stride"]) == (3, 2)
    assert spec.out_shape[:2] == (size, size)


def test_stage_three_is_the_fig10_network():
    """The paper's Fig. 10 network is a cut of GoogLeNet: its two inception
    modules are stage 3's, layer for layer."""
    full = googlenet()
    for l in inception_net(224).layers:
        if l.name.startswith("inception_"):
            module, part = l.name.split("/")
            tag = {"inception_1": "3a", "inception_2": "3b"}[module]
            g = full.spec(f"inception_{tag}/{part}")
            assert (g.op, g.out_shape, dict(g.attrs)) == (l.op, l.out_shape, dict(l.attrs))


@pytest.fixture
def slice_search_events():
    events = []

    def listen(event, duration, **attrs):
        if event == "/repro/plan/slice_search":
            events.append(attrs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    yield events
    jax.monitoring.unregister_event_duration_listener(listen)


def test_slice_search_counters_add_up(slice_search_events, monkeypatch):
    """``schedules`` is the number of distinct factor maps the search sliced
    and scheduled, ``tasks`` the result's sliced layer count; a seed given
    twice is priced once and read back from the memo."""
    model = inception_net(32)
    priced = []
    slice_model = slicing.slice_model

    def recording(m, factors, *a, **k):
        priced.append(frozenset(factors.items()))
        return slice_model(m, factors, *a, **k)

    def counting(dag, m):
        counting.calls += 1
        return dsh(dag, m)

    counting.calls = 0
    monkeypatch.setattr(slicing, "slice_model", recording)
    factors = slicing.search_slice_factors(model, TPU_V5E, m=1, heuristic=counting,
                                           seeds=(4,))
    monkeypatch.setattr(slicing, "slice_model", slice_model)
    (counts,) = slice_search_events
    assert counts["schedules"] == counting.calls == len(priced) == len(set(priced))
    assert counts["layers"] == len(model.layers)
    assert counts["sliced_layers"] == len(factors)
    assert counts["tasks"] == len(slice_model(model, factors).layers)

    again = slicing.search_slice_factors(model, TPU_V5E, m=1, seeds=(4, 4))
    assert again == factors
    twice = slice_search_events[-1]
    assert twice["schedules"] == counts["schedules"]
    assert twice["memo_hits"] == counts["memo_hits"] + 2

