"""Plain blocked Cholesky: the reference that decides a Cholesky cell's
``correct``, and its control.

It imports nothing of the program under test. It works on the matrix in
block layout, ``a[i, j]`` the ``b x b`` block at block row ``i`` and block
column ``j``, right-looking:

    L_kk = chol(A_kk)
    L_ik = A_ik L_kk^-T                       for i > k
    A_ij -= L_ik L_jk^T                       for i > k, every j

Only the lower blocks of ``a`` are read. Every matmul of the panel and of
the trailing update goes through one ``dot`` (an ``einsum``), so the whole
factorisation runs in the precision that ``dot`` gives:

    highest   float32 products (``Precision.HIGHEST``): the reference.
    high      three bf16 passes, written out: x = hi + lo with hi, lo in
              bf16, and x y ~ hi_x hi_y + hi_x lo_y + lo_x hi_y summed in
              float32. This is what ``Precision.HIGH`` does on a TPU, and
              written out it does the same on the CPU. It is the control,
              the step below the configuration's ``highest``.

The b x b diagonal factor and its inverse stay in float32 in both: they
are 1/nb of the panel work and none of the trailing update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def dot_highest(spec: str, x, y):
    return jnp.einsum(spec, x, y, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def dot_high(spec: str, x, y):
    def split(v):
        hi = v.astype(jnp.bfloat16)
        return hi, (v - hi.astype(v.dtype)).astype(jnp.bfloat16)

    (xh, xl), (yh, yl) = split(x), split(y)

    def e(p, q):
        return jnp.einsum(spec, p, q, preferred_element_type=jnp.float32)

    return e(xh, yh) + e(xh, yl) + e(xl, yh)


DOTS = {"highest": dot_highest, "high": dot_high}
# the control's precision: the step below the one a configuration states
BELOW = {"highest": "high"}


@functools.partial(jax.jit, static_argnames=("precision",), donate_argnums=0)
def cholesky_blocks(a, precision: str = "highest"):
    """The lower Cholesky factor of the SPD matrix whose lower blocks are in
    ``a`` ([nb, nb, b, b]); returned in the same layout, lower blocks only
    meaningful."""
    dot = DOTS[precision]
    nb, _, b, _ = a.shape
    eye = jnp.eye(b, dtype=a.dtype)
    block_rows = jnp.arange(nb)[:, None, None]

    def panel(k, a):
        akk = jnp.tril(a[k, k])
        lkk = jnp.linalg.cholesky(akk + jnp.tril(akk, -1).T)
        linv = jax.scipy.linalg.solve_triangular(lkk, eye, lower=True)
        col = a[:, k]                                    # [nb, b, b]
        below = block_rows > k
        lcol = jnp.where(below, dot("iab,cb->iac", col, linv), 0)

        def update(i, a):
            return a.at[i].add(-dot("ab,jcb->jac", lcol[i], lcol))

        a = lax.fori_loop(k + 1, nb, update, a)
        return a.at[:, k].set(jnp.where(below, lcol, col).at[k].set(lkk))

    return lax.fori_loop(0, nb, panel, a)


@functools.partial(jax.jit, static_argnames=("nb",))
def assemble(blocks, rows, cols, nb: int):
    """[nb, nb, b, b] with ``blocks[t]`` at (rows[t], cols[t]), zero
    elsewhere; entries whose row is ``nb`` are padding and dropped."""
    b = blocks.shape[-1]
    a = jnp.zeros((nb, nb, b, b), blocks.dtype)
    return a.at[rows, cols].set(blocks, mode="drop")


@jax.jit
def max_rel_err(l_blocks, rows, cols, l_ref):
    """max |L - L_ref| over the blocks ``l_blocks[t]`` at (rows[t],
    cols[t]), over max |L_ref| over the same blocks; block by block, so no
    second copy of the factor is made. Rows equal to nb are padding."""
    nb = l_ref.shape[0]

    def body(t, acc):
        ok = rows[t] < nb
        ref = l_ref[jnp.minimum(rows[t], nb - 1), cols[t]]
        err = jnp.where(ok, jnp.max(jnp.abs(l_blocks[t] - ref)), 0)
        scale = jnp.where(ok, jnp.max(jnp.abs(ref)), 0)
        return jnp.maximum(acc[0], err), jnp.maximum(acc[1], scale)

    zero = jnp.zeros((), l_ref.dtype)
    err, scale = lax.fori_loop(0, rows.shape[0], body, (zero, zero))
    return err / scale
