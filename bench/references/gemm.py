"""Plain blocked C = A B: the reference that decides a GEMM cell's
``correct``, and its control.

It imports nothing of the program under test. It works on the matrices in
block layout, ``a[i, k]`` the ``b x b`` block at block row ``i`` and block
column ``k``, one block row of C at a time, summing over k in order:

    C_ij = 0;  C_ij += A_ik B_kj              for k = 0, 1, ..., every j

Each block product is one ``dot`` (an ``einsum``), in the precision that
``dot`` gives: ``highest`` (float32 products, ``references/cholesky.py``'s
``dot_highest``) for the reference, ``high`` (three bf16 passes, written
out) for the control, the step below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.references.cholesky import BELOW, dot_highest  # noqa: F401


def dot_high(spec: str, x, y):
    """bf16_3x: each operand split into two bf16 parts, all products but
    the two low parts' taken, in float32. The split rounds with
    ``reduce_precision``, which XLA keeps: a float32 -> bf16 -> float32
    round trip by ``astype`` may keep its float32 bits on a TPU (XLA allows
    excess precision), and the low parts then come out zero, one bf16
    pass in all."""
    def split(v):
        hi = lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        lo = lax.reduce_precision(v - hi, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    (xh, xl), (yh, yl) = split(x), split(y)

    def e(p, q):
        return jnp.einsum(spec, p, q, preferred_element_type=jnp.float32)

    return e(xh, yh) + e(xh, yl) + e(xl, yh)


DOTS = {"highest": dot_highest, "high": dot_high}


@functools.partial(jax.jit, static_argnames=("precision",))
def gemm_blocks(a, b, precision: str = "highest"):
    """C = A B for ``a``, ``b`` in block layout ([nb, nb, bs, bs]); returned
    in the same layout."""
    dot = DOTS[precision]

    def row(a_i):                                   # [nb (k), bs, bs]
        def step(c_i, ab):
            a_ik, b_k = ab                      # [bs, bs], [nb (j), bs, bs]
            return c_i + dot("ac,jcd->jad", a_ik, b_k), None

        return lax.scan(step, jnp.zeros_like(b[0]), (a_i, b))[0]

    return lax.map(row, a)
