"""Tiled MXU matmul — the paper's GEMM hotspot as a Pallas TPU kernel.

TPU adaptation of the paper's per-task `C_ij += A_ik · B_kj` body: instead of
a cache-blocked CPU GEMM, the block is tiled for VMEM with an explicit
(M/bm, N/bn, K/bk) grid. K is the innermost (sequential) grid dimension so a
VMEM f32 scratch accumulator carries partial sums across K steps — HBM sees
each A/B tile exactly once per (i,j) and the C tile exactly once (written at
the last K step), which pushes arithmetic intensity into the bm·bn·bk regime
the MXU needs. Tile defaults (256, 256, 256) are multiples of the 128×128
MXU systolic array; A+B+acc tiles ≈ 768 KiB of VMEM, leaving room for
double buffering in ~16 MiB/core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def block_gemm(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256,
               bn: int = 256, bk: int = 256,
               interpret: bool = False) -> jnp.ndarray:
    """C[M,N] = A[M,K] @ B[K,N] (f32 accumulate, output in A's dtype)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k})x({k},{n}) not divisible by tiles ({bm},{bn},{bk})")
    k_steps = k // bk

    return pl.pallas_call(
        functools.partial(_gemm_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        # f32 VMEM accumulator carried across the sequential K dimension
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)


def _indexed_kernel(local_hbm, tbl, o_ref, a_buf, b_buf, c_buf, acc_ref,
                    sem, *, bm: int, bn: int, bk: int):
    """One (t, i, j, k) step of ``out[t] = local[c] - local[a] local[b]^T``.

    The operand tiles stay in HBM until this kernel fetches them by the
    table's slot numbers: A and B tiles double-buffered one step ahead, the
    C tile one output tile ahead. The grid runs in order (every dimension
    ``arbitrary``), so each step starts the next step's copies before it
    waits on its own."""
    t, i, j, k = (pl.program_id(d) for d in range(4))
    n_i, n_j, n_k = (pl.num_programs(d) for d in range(1, 4))
    n_tiles = pl.num_programs(0) * n_i * n_j
    tile = (t * n_i + i) * n_j + j
    step = tile * n_k + k

    def where(tl):                   # output tile number -> (t, i, j)
        return tl // (n_i * n_j), (tl // n_j) % n_i, tl % n_j

    def ab_copies(s):
        (t_, i_, j_), k_, slot = where(s // n_k), s % n_k, s % 2
        return (
            pltpu.make_async_copy(
                local_hbm.at[tbl[3 * t_ + 1], pl.ds(i_ * bm, bm),
                             pl.ds(k_ * bk, bk)],
                a_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(
                local_hbm.at[tbl[3 * t_ + 2], pl.ds(j_ * bn, bn),
                             pl.ds(k_ * bk, bk)],
                b_buf.at[slot], sem.at[1, slot]))

    def c_copy(tl):
        t_, i_, j_ = where(tl)
        return pltpu.make_async_copy(
            local_hbm.at[tbl[3 * t_], pl.ds(i_ * bm, bm), pl.ds(j_ * bn, bn)],
            c_buf.at[tl % 2], sem.at[2, tl % 2])

    @pl.when(step == 0)
    def _first():
        for cp in ab_copies(0):
            cp.start()
        c_copy(0).start()

    @pl.when(step + 1 < n_tiles * n_k)
    def _next_ab():
        for cp in ab_copies(step + 1):
            cp.start()

    @pl.when((k == 0) & (tile + 1 < n_tiles))
    def _next_c():
        c_copy(tile + 1).start()

    for cp in ab_copies(step):
        cp.wait()

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # A B^T: both operands contract their last dim, no transpose is made
    acc_ref[...] += jax.lax.dot_general(
        a_buf[step % 2], b_buf[step % 2], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        c_copy(tile).wait()
        o_ref[...] = (c_buf[tile % 2] - acc_ref[...]).astype(o_ref.dtype)


def _fit_tile(tile: int, m: int) -> int:
    """The largest of ``tile``, ``tile/2``, ... down to 128 that divides the
    block width ``m`` (``m`` itself where it is no wider than ``tile``, or
    where none divides it)."""
    t = min(tile, m)
    while m % t and t > 128:
        t //= 2
    return t if m % t == 0 else m


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def block_gemm_indexed(local: jnp.ndarray, table: jnp.ndarray, *,
                       bm: int = 512, bn: int = 512, bk: int = 512,
                       interpret: bool = False) -> jnp.ndarray:
    """out[t] = local[c] - local[a] @ local[b]^T for each row (c, a, b) of
    the int table ``[T, 3]``, reading the blocks of ``local`` ``[n, m, m]``
    where they lie (f32 accumulate, output ``[T, m, m]`` in local's dtype).

    The trailing update of a tiled factorisation straight from the slot
    array: no operand block is gathered, stacked or transposed before the
    kernel runs. The slot array is the call's first operand and stays in
    HBM; the table goes to SMEM flattened (a 2D SMEM array pads each row to
    128 words). Every row is computed, padding rows too. The tiles are
    twice ``block_gemm``'s: each step issues its own copies and index
    arithmetic, which 512-wide tiles amortise over 8x the MXU work (a
    1024-wide row took 71.6 us against 93.4 us with 256-wide tiles on a
    TPU v5e), and A, B, C, output and accumulator still fit the default
    16 MiB of scoped VMEM. A tile that does not divide the block halves
    until it does (768-wide blocks take 256-wide tiles)."""
    _, m, k = local.shape
    assert m == k, local.shape
    bm, bn, bk = (_fit_tile(t, m) for t in (bm, bn, bk))
    n_rows = table.shape[0]
    dt = local.dtype
    return pl.pallas_call(
        functools.partial(_indexed_kernel, bm=bm, bn=bn, bk=bk),
        grid=(n_rows, m // bm, m // bn, k // bk),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((None, bm, bn), lambda t, i, j, kk: (t, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_rows, m, m), dt),
        scratch_shapes=[pltpu.VMEM((2, bm, bk), dt),       # A tiles
                        pltpu.VMEM((2, bn, bk), dt),       # B tiles
                        pltpu.VMEM((2, bm, bn), dt),       # C tiles
                        pltpu.VMEM((bm, bn), jnp.float32),  # accumulator
                        pltpu.SemaphoreType.DMA((3, 2))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4),
        interpret=interpret,
    )(local, table.astype(jnp.int32).reshape(-1))
