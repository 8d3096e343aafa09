"""The four-chip cell ``inception224.m4.closed1``: its configuration is the
one-chip network with a deployment of its own, and its limits catch the
control."""
from chipbench import harness, reference
from chipbench.tests.conftest import small_inception

CELL = "inception224.m4.closed1"


def test_four_chip_configuration_is_inception224_planned_over_four_chips(bench):
    cell = harness.load_cell(bench, CELL)
    four = reference.load_config(cell["config"])
    one = reference.load_config("inception224")
    assert cell["m"] == cell["chips"] == 4
    assert four["deployment"]
    # the source names the paper, then the part that defines this deployment
    assert four["source"].startswith(one["source"] + " ")
    assert four["source"] != one["source"]
    own = ("name", "source", "deployment")
    assert ({k: v for k, v in four.items() if k not in own}
            == {k: v for k, v in one.items() if k not in own})


def test_control_fails_the_four_chip_limit(bench):
    """The reference at ``high`` precision in the program's place reads
    above one of the cell's limits, on three seeds."""
    limits = harness.load_cell(bench, CELL)["limits"]
    cfg = small_inception(32)
    for seed in (4, 5, 2**31 + 6):
        params = reference.make_params(cfg, seed)
        xs = reference.input_pool(cfg, 8, seed)
        ref = reference.outputs(cfg, params, xs, "highest", block=8)
        got = reference.compare(reference.outputs(cfg, params, xs, "high", block=8), ref)
        assert any(got[k] > lim for k, lim in limits.items()), got
