"""The reduction from a device trace to busy, idle and collective time."""
import glob
import gzip
import json
import os

import pytest

from chipbench import tracereduce

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")

SYNTHETIC = {
    "host": [
        ["window", 1000, 10000],
        ["step", 2500, 2700],
        ["fetch", 6000, 1000],
        ["submit", 7000, 3600],
    ],
    "devices": {
        "0": [
            ["A.1", 500, 1500],
            ["B.2", 1500, 1500],
            ["collective-permute-start.1", 5000, 900],
            ["%while.3 = f32[4]{0} while(f32[4]{0} %x)", 6500, 3000],
            ["%fusion.9 = f32[4]{0} fusion(f32[4]{0} %y)", 7000, 1200],
            ["C", 10500, 1500],
        ],
        "1": [["D.7", 2000, 2000]],
    },
}


def test_merge_unions_overlaps():
    assert tracereduce.merge([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == [(1, 4.5), (5, 6)]


def test_synthetic_trace():
    r = tracereduce.reduce(SYNTHETIC)
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busiest_device"] == "0"
    assert r["busiest_busy_s"] == pytest.approx(6400e-9)
    assert r["busy_s"] == pytest.approx((6400 + 2000) / 2 * 1e-9)
    assert r["idle_share"] == pytest.approx((0.36 + 0.8) / 2)
    assert r["busiest_collective_s"] == pytest.approx(900e-9)
    # ranked by self time: the while loop less the fusion nested in it
    assert [n for n, _ in r["top_ops"]] == [
        "while.3 f32[4]", "B.2", "fusion.9 f32[4]", "A.1", "collective-permute-start.1", "C"]
    assert [t for _, t in r["top_ops"]] == pytest.approx([1.8e-6, 1.5e-6, 1.2e-6, 1e-6, 9e-7, 5e-7])
    assert [n for n, _ in r["idle_gaps"]] == [
        "total:step", "total:submit", "total:fetch",
        "longest:step", "longest:submit", "longest:fetch"]
    assert [t for _, t in r["idle_gaps"]] == pytest.approx([2e-6, 1e-6, 6e-7, 2e-6, 1e-6, 6e-7])


def test_labels_keep_name_and_shape():
    assert tracereduce.label("%fusion.23 = f32[112,64]{1,0:T(8,128)} fusion(%a)") == "fusion.23 f32[112,64]"
    assert tracereduce.label("%cond.5 = (f32[1]{0}, s32[]) conditional(%p)") == "cond.5"


def test_one_window_is_required():
    bad = dict(SYNTHETIC, host=[h for h in SYNTHETIC["host"] if h[0] != "window"])
    with pytest.raises(ValueError):
        tracereduce.reduce(bad)


def _recorded():
    return sorted(glob.glob(os.path.join(FIXTURES, "*.json.gz")))


@pytest.mark.parametrize("path", _recorded(), ids=os.path.basename)
def test_recorded_chip_trace(path):
    """A short trace recorded on a TPU v5e reduces to the numbers read when
    it was recorded, and they are sound: busy time inside the window, idle
    share between 0 and 1."""
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    r = tracereduce.reduce(rec["trace"])
    for key in ("window_s", "busy_s", "busiest_busy_s", "busiest_collective_s", "idle_share"):
        assert r[key] == pytest.approx(rec["reduced"][key], rel=1e-12), key
    assert r["top_ops"] == rec["reduced"]["top_ops"]
    assert 0 < r["busiest_busy_s"] <= r["window_s"]
    assert 0 <= r["idle_share"] < 1
    assert rec["completed"] > 0
