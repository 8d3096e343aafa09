"""What entry points set up before the first compile: the hardware spec
that prices a plan for the device found, and the persistent compile cache."""
import os

import pytest

from repro.core.costmodel import TPU_V5E, hardware_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_v5e_device_kind_prices_as_v5e():
    assert hardware_for("TPU v5 lite") is TPU_V5E


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "cpu", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(ValueError, match="no HardwareSpec"):
        hardware_for(kind)


# importing the library sets no cache; the helper returns what JAX uses
_CACHE = """
import jax
import repro.launch.analysis
print(jax.config.jax_compilation_cache_dir)
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_defaults_to_the_repo(subproc, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    at_import, returned, used = subproc(_CACHE).split()
    assert at_import == "None"
    assert returned == used == os.path.join(REPO, ".jax_cache")


def test_compile_cache_keeps_the_environment_dir(subproc, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    at_import, returned, used = subproc(_CACHE).split()
    assert at_import == returned == used == str(tmp_path)
