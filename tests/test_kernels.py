"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.block_gemm.block_gemm import (block_gemm,
                                                 block_gemm_indexed)
from repro.kernels.block_gemm.ref import block_gemm_ref
from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_ref
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------- block_gemm

@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (64, 64, 64, 32, 32, 32),
    (128, 64, 96, 64, 32, 32),
    (32, 128, 64, 32, 128, 64),   # single tile in two dims
    (256, 256, 128, 128, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_gemm_sweep(m, n, k, bm, bn, bk, dtype):
    k1, k2 = jax.random.split(jax.random.key(0))
    a = _rand(k1, (m, k), dtype)
    b = _rand(k2, (k, n), dtype)
    got = block_gemm(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    want = block_gemm_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def _update_ref(local, table):
    """local[c] - local[a] @ local[b]^T per row (c, a, b), in float64."""
    x = np.asarray(local, np.float64)
    c, a, b = np.asarray(table).T
    return x[c] - np.einsum("tik,tjk->tij", x[a], x[b])


@pytest.mark.parametrize("b,tiles", [
    (128, (64, 64, 64)),          # 2 x 2 output tiles, 2 K steps each
    (128, (128, 128, 128)),       # one tile, one step per row
    (256, (128, 128, 64)),
    (256, (256, 256, 256)),
], ids=["b128_t64", "b128_t128", "b256_t128x64", "b256_t256"])
def test_block_gemm_indexed_matches_the_update(b, tiles):
    """Each row of the table reads its three blocks from the slot array by
    index: out[t] = local[c] - local[a] @ local[b]^T, in float32."""
    rng = np.random.default_rng(b + tiles[2])
    local = jnp.asarray(rng.standard_normal((9, b, b)) / np.sqrt(b),
                        jnp.float32)
    table = rng.integers(0, 9, (5, 3)).astype(np.int32)
    bm, bn, bk = tiles
    got = block_gemm_indexed(local, jnp.asarray(table), bm=bm, bn=bn, bk=bk,
                             interpret=True)
    assert got.shape == (5, b, b) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _update_ref(local, table),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("table", [
    [[4, 1, 1], [5, 2, 2]],            # syrk's rows: A - L L^T, L twice
    [[6, 6, 6], [0, 2, 3], [6, 6, 6]],  # padding rows on the trash slot
    [[3, 0, 1]],                       # T = 1
], ids=["repeated", "trash", "one_row"])
def test_block_gemm_indexed_tables(table):
    """Repeated operands, padding rows that all point at the trash slot (the
    last one) and computed like any other, and a one-row table."""
    rng = np.random.default_rng(len(table))
    local = jnp.asarray(rng.standard_normal((7, 128, 128)) / np.sqrt(128),
                        jnp.float32)
    table = np.asarray(table, np.int16)       # the executor's narrow tables
    got = block_gemm_indexed(local, jnp.asarray(table), bm=64, bn=64, bk=64,
                             interpret=True)
    np.testing.assert_allclose(got, _update_ref(local, table),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("body", ["syrk", "gemm"])
def test_cholesky_update_bodies_at_b768(body):
    """A block width that the 512-wide default tiles do not divide still
    runs (768 takes 256-wide tiles), through the indexed form the Cholesky
    bodies carry with ``task_matmul``."""
    from repro.kernels.block_gemm.ops import task_matmul
    from repro.linalg.cholesky import cholesky_bodies
    rng = np.random.default_rng(768)
    local = jnp.asarray(rng.standard_normal((4, 768, 768)) / np.sqrt(768),
                        jnp.float32)
    ops = np.asarray({"syrk": [[3, 0], [2, 1]],
                      "gemm": [[3, 0, 1], [2, 1, 0]]}[body], np.int32)
    got = cholesky_bodies(task_matmul)[body].from_slots(local,
                                                        jnp.asarray(ops))
    rows = ops if body == "gemm" else np.concatenate([ops, ops[:, 1:]], 1)
    np.testing.assert_allclose(got, _update_ref(local, rows),
                               rtol=2e-5, atol=2e-5)


# -------------------------------------------------------- flash_attention

@pytest.mark.parametrize("b,hq,hkv,lq,lk,d,bq,bk", [
    (1, 4, 4, 128, 128, 64, 64, 64),     # MHA
    (2, 8, 2, 128, 128, 64, 64, 64),     # GQA 4:1
    (1, 4, 1, 64, 256, 32, 64, 64),      # MQA, kv longer than q
    (1, 2, 2, 256, 256, 128, 128, 64),   # uneven q/kv tiles
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, lq, lk, d, bq, bk, causal, dtype):
    keys = jax.random.split(jax.random.key(1), 3)
    q = _rand(keys[0], (b, hq, lq, d), dtype)
    k = _rand(keys[1], (b, hkv, lk, d), dtype)
    v = _rand(keys[2], (b, hkv, lk, d), dtype)
    got = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                          interpret=True)
    want = mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_matches_oracle_on_long_seq():
    q = _rand(jax.random.key(2), (1, 2, 512, 64), jnp.float32)
    k = _rand(jax.random.key(3), (1, 2, 512, 64), jnp.float32)
    v = _rand(jax.random.key(4), (1, 2, 512, 64), jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=128, bk=128,
                          interpret=True)
    np.testing.assert_allclose(got, mha_ref(q, k, v, causal=True),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------- task-body wrappers

def test_task_matmul_vmaps_to_fused_grid():
    """`task_matmul` is the executor's per-task body form: vmapping it (what
    the wavefront compute step does over the task table) folds the batch
    into a leading pallas grid dimension and still matches the oracle."""
    from repro.kernels.block_gemm.ops import task_matmul

    keys = jax.random.split(jax.random.key(9), 2)
    a = _rand(keys[0], (5, 16, 16), jnp.float32)
    b = _rand(keys[1], (5, 16, 16), jnp.float32)
    got = jax.vmap(task_matmul)(a, b)
    np.testing.assert_allclose(got, jnp.einsum("bij,bjk->bik", a, b),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_task_attention_matches_ref(causal):
    """2D-block single-head attention body vs the jnp oracle, including
    under vmap (the executor's batching over a wavefront's task table)."""
    from repro.kernels.flash_attention.ops import task_attention

    keys = jax.random.split(jax.random.key(10), 3)
    q = _rand(keys[0], (3, 32, 16), jnp.float32)
    k = _rand(keys[1], (3, 32, 16), jnp.float32)
    v = _rand(keys[2], (3, 32, 16), jnp.float32)
    got = jax.vmap(lambda q_, k_, v_: task_attention(
        q_, k_, v_, causal=causal))(q, k, v)
    want = mha_ref(q[:, None], k[:, None], v[:, None], causal=causal)[:, 0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -------------------------------------------------------- decode_attention

@pytest.mark.parametrize("b,hq,hkv,s,d,bs", [
    (2, 8, 2, 256, 64, 64),
    (1, 4, 4, 512, 128, 128),
    (4, 16, 1, 128, 64, 64),  # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, hq, hkv, s, d, bs, dtype):
    keys = jax.random.split(jax.random.key(5), 3)
    q = _rand(keys[0], (b, hq, d), dtype)
    k = _rand(keys[1], (b, hkv, s, d), dtype)
    v = _rand(keys[2], (b, hkv, s, d), dtype)
    got = decode_attention(q, k, v, bs=bs, interpret=True)
    want = decode_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_ragged_lengths():
    b, hq, hkv, s, d = 3, 4, 2, 256, 64
    keys = jax.random.split(jax.random.key(6), 3)
    q = _rand(keys[0], (b, hq, d), jnp.float32)
    k = _rand(keys[1], (b, hkv, s, d), jnp.float32)
    v = _rand(keys[2], (b, hkv, s, d), jnp.float32)
    kv_len = jnp.array([256, 100, 17], jnp.int32)
    got = decode_attention(q, k, v, kv_len, bs=64, interpret=True)
    want = decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------- ssd_scan

@pytest.mark.parametrize("b,l,h,g,p,n,q", [
    (1, 128, 2, 1, 32, 16, 64),
    (2, 256, 4, 2, 64, 32, 128),
    (1, 64, 8, 8, 16, 16, 32),   # one head per group
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, l, h, g, p, n, q, dtype):
    keys = jax.random.split(jax.random.key(7), 5)
    x = _rand(keys[0], (b, l, h, p), dtype)
    dt = jax.nn.softplus(_rand(keys[1], (b, l, h), jnp.float32)) * 0.1
    a = -jnp.exp(_rand(keys[2], (h,), jnp.float32) * 0.5)
    bmat = _rand(keys[3], (b, l, g, n), dtype) * 0.5
    cmat = _rand(keys[4], (b, l, g, n), dtype) * 0.5
    d = jnp.ones((h,), jnp.float32) * 0.5
    got = ssd_scan(x, dt.astype(dtype), a, bmat, cmat, d, q_chunk=q,
                   interpret=True)
    want = ssd_ref(x, dt.astype(dtype), a, bmat, cmat, d)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_ssd_scan_state_carries_across_chunks():
    """Chunked result must match the recurrence even when L >> chunk."""
    b, l, h, g, p, n = 1, 256, 2, 1, 16, 8
    keys = jax.random.split(jax.random.key(8), 5)
    x = _rand(keys[0], (b, l, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(keys[1], (b, l, h), jnp.float32)) * 0.2
    a = -jnp.exp(_rand(keys[2], (h,), jnp.float32) * 0.3)
    bmat = _rand(keys[3], (b, l, g, n), jnp.float32) * 0.5
    cmat = _rand(keys[4], (b, l, g, n), jnp.float32) * 0.5
    got32 = ssd_scan(x, dt, a, bmat, cmat, None, q_chunk=32, interpret=True)
    got128 = ssd_scan(x, dt, a, bmat, cmat, None, q_chunk=128, interpret=True)
    want = ssd_ref(x, dt, a, bmat, cmat, None)
    np.testing.assert_allclose(got32, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got128, want, rtol=2e-4, atol=2e-4)
