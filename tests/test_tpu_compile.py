"""The plan path at the paper's full width compiles for a TPU v5e.

Compiles for a described ``v5e:2x2`` topology with no chip attached: the
TPU compiler refuses here, at no chip time, what it would refuse on the
chip (memory, shapes, collectives).  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.  Keep these compiles in this one file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.codegen import build_mpmd_executor, build_plan
from repro.codegen.plan import coalesce_transfer_steps
from repro.core import dsh
from repro.core.costmodel import TPU_V5E
from repro.models.cnn import inception_net, run_sequential
from repro.models.slicing import search_slice_factors, slice_model

INPUT_HW = 224  # paper Fig. 10


@pytest.fixture(scope="module")
def topo():
    # keep the TPU compiler's logs out of /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off around these
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def model_params():
    model = inception_net(INPUT_HW)
    return model, model.init_params(jax.random.PRNGKey(0))


def _sliced_plan(model, m):
    sliced = slice_model(model, search_slice_factors(model, TPU_V5E, m=m))
    dag = sliced.to_dag(TPU_V5E, time_unit=1e-6)
    return sliced, coalesce_transfer_steps(build_plan(dsh(dag, m), dag))


def _input(sharding):
    return jax.ShapeDtypeStruct(
        (1, INPUT_HW, INPUT_HW, 3), jnp.float32, sharding=sharding
    )


def test_sequential_compiles_on_one_chip(topo, no_compile_cache, model_params):
    model, params = model_params
    one_chip = SingleDeviceSharding(topo.devices[0])
    p_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params,
    )
    seq = jax.jit(lambda p, x: run_sequential(model, p, x))
    compiled = seq.lower(p_shapes, _input(one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_sliced_m1_checkpoint_executor_compiles(
    topo, no_compile_cache, model_params
):
    model, params = model_params
    sliced, plan = _sliced_plan(model, 1)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("workers",))
    f = build_mpmd_executor(
        plan, sliced, params, mesh, batch=1, checkpoint=True
    )
    compiled = f.lower(_input(NamedSharding(mesh, PartitionSpec()))).compile()
    assert compiled.memory_analysis() is not None


def test_sliced_m4_executor_compiles_with_collective_permute(
    topo, no_compile_cache, model_params
):
    model, params = model_params
    sliced, plan = _sliced_plan(model, 4)
    assert plan.n_transfers > 0
    mesh = Mesh(np.asarray(topo.devices[:4]), ("workers",))
    # checkpoint=True: the executor Frontend(m=4) serves through
    f = build_mpmd_executor(
        plan, sliced, params, mesh, batch=1, checkpoint=True
    )
    compiled = f.lower(_input(NamedSharding(mesh, PartitionSpec()))).compile()
    assert "collective-permute" in compiled.as_text()
