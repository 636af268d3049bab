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

from bench.devtrace import parse_instruction
from bench.families import gemm as gemm_family
from bench.families.cholesky import is_kernel, kernel_cost
from repro.kernels.block_gemm import ops as gemm_ops
from repro.kernels.block_gemm.block_gemm import block_gemm, block_gemm_indexed
from repro.kernels.flash_attention import ops as attn_ops
from repro.linalg.cholesky import cholesky_executor, cholesky_graph
from repro.linalg.gemm import gemm_2d_graph, gemm_executor


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


@pytest.mark.parametrize("b", [768, 1024])
def test_indexed_block_gemm_compiles_for_v5e(topo, b):
    """The indexed trailing update at its default 512-wide tiles (b = 1024)
    and at the 256-wide tiles they halve to where 512 does not divide the
    block (b = 768), reading from a slot array of ten blocks."""
    local = _on_one_chip(topo, (10, b, b))
    table = jax.ShapeDtypeStruct((6, 3), jnp.int32,
                                 sharding=local.sharding)
    run = jax.jit(lambda x, t: block_gemm_indexed(x, t, interpret=False))
    with jax.default_matmul_precision("highest"):
        text = run.lower(local, table).compile().as_text()
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
    # syrk and gemm read their blocks from the slot array: each kernel call
    # takes the whole [n_slots, b, b] state first and writes [T, b, b], so
    # the benchmark counts 2 T b^3 flops for it; no [T, 3, b, b] operand
    # stack is built for gemm any more
    calls = [op for op in map(parse_instruction, text.splitlines())
             if op is not None and is_kernel(op)]
    assert calls
    for op in calls:
        assert op.operand_shapes[0] == (prog.n_slots, b, b), op
        n_rows = op.shape[0]
        assert op.shape == (n_rows, b, b), op
        flops, _ = kernel_cost(op.shape, op.operand_shapes)
        assert flops == 2 * n_rows * b ** 3
    gemm_rows = max(op.shape[0] for op in calls)
    assert f"f32[{gemm_rows},3,{b},{b}]" not in text
    if pr * pc > 1:
        assert "collective-permute" in text or "all-to-all" in text
        assert "ttor.exchange" in text


def test_gemm_executor_compiles_for_v5e(topo, compiled_bodies):
    """The GEMM executor on one chip: every gemm body gathers its operands
    and runs the vmapped kernel, whose calls the GEMM family finds and
    costs at 2 N^3 flops in all (the unrolled rows carry no padding)."""
    nb, b = 4, 512
    prog = gemm_2d_graph(nb, 1, 1, b).to_program()
    mesh = Mesh(np.array(topo.devices[:1]), ("shards",))
    x = jax.ShapeDtypeStruct((1, prog.n_slots, b, b), jnp.float32,
                             sharding=NamedSharding(mesh, P("shards")))
    run = jax.jit(gemm_executor(prog, mesh, matmul=gemm_ops.task_matmul))
    text = run.lower(x).compile().as_text()
    assert "ttor.gather" in text and "ttor.body.gemm" in text
    calls = [op for op in map(parse_instruction, text.splitlines())
             if op is not None and gemm_family.is_kernel(op)]
    assert len(calls) == nb
    assert sum(gemm_family.kernel_cost(op.shape, op.operand_shapes)[0]
               for op in calls) == 2 * (nb * b) ** 3
