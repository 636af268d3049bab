"""Public jit'd wrapper for the block GEMM kernel.

On the TPU the Pallas body compiles to Mosaic; on the CPU `task_matmul`
runs it in interpret mode for validation. `matmul` auto-selects and falls
back to the jnp oracle off the TPU and for shapes that do not tile
cleanly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from .block_gemm import block_gemm, block_gemm_indexed
from .ref import block_gemm_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256, bn: int = 256,
           bk: int = 256) -> jnp.ndarray:
    """Drop-in `a @ b` with the Pallas path where it applies."""
    m, k = a.shape
    _, n = b.shape
    tiles_ok = (m % min(bm, m) == 0 and n % min(bn, n) == 0
                and k % min(bk, k) == 0 and m >= 8 and n >= 128 and k >= 8)
    if _on_tpu() and tiles_ok:
        return block_gemm(a, b, bm=bm, bn=bn, bk=bk)
    return block_gemm_ref(a, b)


def task_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256,
                bn: int = 256, bk: int = 256) -> jnp.ndarray:
    """Per-task ``a @ b`` body for the block executor's compute step.

    Unlike :func:`matmul` this never falls back to the jnp oracle — it is
    *always* the Pallas kernel (Mosaic on the TPU, interpret mode on the
    CPU, an error on any other backend),
    so plugging it into ``gemm_bodies(matmul=task_matmul)`` /
    ``cholesky_bodies(matmul=task_matmul)`` exercises the kernel path end
    to end. The executor vmaps task bodies over each wavefront's task
    table, and ``vmap(pallas_call)`` folds the batch into a leading grid
    dimension: all of a wavefront's trailing updates become one fused
    kernel launch. Tile sizes clamp to the block shape, so the paper-scale
    b×b task blocks run as a single-tile grid.

    ``task_matmul.indexed_update`` is :func:`task_update`: the same kernel
    in the form that reads a trailing update's blocks from the slot array.
    """
    return block_gemm(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret_mode())


def task_update(local: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """``out[t] = local[c] - local[a] @ local[b].T`` for each row (c, a, b)
    of ``table`` ``[T, 3]``: a wavefront's trailing updates as one kernel
    launch that reads its operand blocks where they lie in the executor's
    slot array ``local`` ``[n_slots, b, b]``, instead of from a gathered
    ``[T, 3, b, b]`` copy. Always the Pallas kernel, as ``task_matmul``."""
    return block_gemm_indexed(local, table, interpret=interpret_mode())


task_matmul.indexed_update = task_update
