"""Fixtures of the chip benchmark's own tests, which run on the CPU."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def bench():
    from chipbench import harness

    return harness.load_benchmark()


def small_inception(hw: int = 32) -> dict:
    """``inception224`` at a ``hw`` x ``hw`` input, every width as published."""
    from chipbench import reference

    cfg = copy.deepcopy(reference.load_config("inception224"))
    cfg["input_shape"] = [hw, hw, 3]
    cfg["program"]["kwargs"]["input_hw"] = hw
    for layer in cfg["layers"]:
        if layer[0] == "avgpool":
            layer[3] = {"kernel": hw // 8, "stride": hw // 8}
    return cfg
