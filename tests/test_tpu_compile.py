"""Compiles for a described TPU v5e: the Pallas task bodies and the compiled
executor that runs them, lowered and compiled by the TPU compiler without a
chip attached. These catch what interpret mode cannot — tiles not aligned to
the chip's layout, kernels over the fast-memory limit, programs that do not
partition — at no chip time. Nothing here runs.

The topology is described inside a module fixture (never at import), so a
process that cannot describe one skips these tests and the other workers of
a parallel run collect the same test list. Code that asks
``jax.default_backend()`` still sees the CPU here, so the tests steer the
task bodies to their compiled (not interpreted) form themselves.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.block_gemm import ops as gemm_ops
from repro.kernels.block_gemm.block_gemm import block_gemm
from repro.kernels.flash_attention import ops as attn_ops
from repro.linalg.cholesky import cholesky_executor, cholesky_graph


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_bodies(monkeypatch):
    """Task bodies in their TPU form: Mosaic, not the CPU's interpreter."""
    monkeypatch.setattr(gemm_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(attn_ops, "interpret_mode", lambda: False)


def _on_one_chip(topo, shape):
    mesh = Mesh(np.array(topo.devices[:1]), ("shards",))
    return jax.ShapeDtypeStruct(shape, jnp.float32,
                                sharding=NamedSharding(mesh, P()))


def test_vmapped_block_gemm_compiles_for_v5e(topo):
    """One wavefront's trailing updates: vmap folds the task batch into the
    kernel's grid, at the 1024x1024 f32 blocks chip_smoke.py runs."""
    a = _on_one_chip(topo, (4, 1024, 1024))
    run = jax.jit(jax.vmap(lambda x, y: block_gemm(x, y, interpret=False)))
    text = run.lower(a, a).compile().as_text()
    assert "tpu_custom_call" in text


def test_task_attention_compiles_for_v5e(topo, compiled_bodies):
    """The attention task body at a real head width (128) and 1024 rows."""
    q = _on_one_chip(topo, (1024, 128))
    text = jax.jit(attn_ops.task_attention).lower(q, q, q).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["one_chip", "2x2"])
def test_cholesky_executor_compiles_for_v5e(topo, compiled_bodies, grid):
    """The compiled executor with Pallas bodies: one chip runs the kernel;
    the 2x2 grid also exchanges blocks with a collective."""
    nb, b = 4, 512
    pr, pc = grid
    prog = cholesky_graph(nb, pr, pc, b).to_program()
    mesh = Mesh(np.array(topo.devices[:pr * pc]), ("shards",))
    x = jax.ShapeDtypeStruct((pr * pc, prog.n_slots, b, b), jnp.float32,
                             sharding=NamedSharding(mesh, P("shards")))
    run = jax.jit(cholesky_executor(prog, mesh,
                                    matmul=gemm_ops.task_matmul))
    text = run.lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's custom call keeps the name the benchmark finds it by
    # (``block_gemm``), under the task body's scope
    assert re.search(r"%\S*block_gemm\S* = \S+ custom-call\(", text)
    assert "ttor.body.gemm" in text
    if pr * pc > 1:
        assert "collective-permute" in text or "all-to-all" in text
        assert "ttor.exchange" in text
