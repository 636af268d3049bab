"""Public jit'd wrapper for flash attention."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from .flash_attention import flash_attention
from .ref import mha_ref


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, bq: int = 512, bk: int = 512) -> jnp.ndarray:
    """Causal GQA attention; Pallas on TPU, jnp oracle elsewhere."""
    lq, lk = q.shape[2], k.shape[2]
    tiles_ok = lq % min(bq, lq) == 0 and lk % min(bk, lk) == 0
    if jax.default_backend() == "tpu" and tiles_ok:
        return flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    return mha_ref(q, k, v, causal=causal)


def task_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   causal: bool = True, bq: int = 512,
                   bk: int = 512) -> jnp.ndarray:
    """Single-head attention over 2D ``[L, D]`` blocks — the block
    executor's task-body form of :func:`flash_attention`.

    Always the Pallas kernel (Mosaic on the TPU, interpret mode on the CPU,
    an error on any other backend), never the jnp oracle, so a PTG whose
    task bodies are attention steps exercises the kernel end to end. The
    executor vmaps bodies over each wavefront's task table;
    ``vmap(pallas_call)`` folds that batch into a leading grid dimension,
    one fused launch per wavefront.
    """
    out = flash_attention(q[None, None], k[None, None], v[None, None],
                          causal=causal, bq=bq, bk=bk,
                          interpret=interpret_mode())
    return out[0, 0]
