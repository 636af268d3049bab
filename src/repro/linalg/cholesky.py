"""Distributed blocked Cholesky as a declarative PTG — the paper's §III-C
flagship app, declared once through the unified ``repro.ptg`` front-end.

Right-looking variant of Algorithm 1, in the PTG form of Fig 8:

    potrf(k):        L_kk   = chol(A_kk)
    trsm(i,k):       L_ik   = A_ik · L_kk^{-T}                (i > k)
    syrk(k,i):       A_ii  -= L_ik · L_ikᵀ                    (i > k)
    gemm(k,i,j):     A_ij  -= L_ik · L_jkᵀ                    (i > j > k)

Each task type declares only the blocks it reads and the block it writes;
the whole dependency web of Fig 8 — panel broadcasts, trailing-update
chains, the syrk→potrf hand-off down the diagonal — is *derived* by the
builder from those access patterns over the factorization's sequential
program order (``Graph.sequence``), with in/out edges mutual inverses by
construction.

Blocks are 2D block-cyclic on a pr×pc grid. Factor blocks L_ik get fresh
block ids (single assignment) because they cross shards: potrf/trsm results
are exactly the payloads the paper ships via (large) active messages, while
the A_ij update accumulations stay owner-local (read-modify-write).
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.schedule import BlockPTGSpec, BlockProgram, build_block_program
from repro.ptg import Graph, IndexSpace


def cholesky_graph(nb: int, pr: int, pc: int, b: int,
                   dtype=jnp.float32) -> Graph:
    def owner(blk) -> int:
        _, i, j = blk
        return (i % pr) * pc + (j % pc)

    g = Graph("cholesky", n_shards=pr * pc, owner=owner,
              block_shape=(b, b), dtype=dtype)
    g.task_type("potrf",
                writes=lambda k: ("L", k, k),
                reads=lambda k: [("A", k, k)])
    g.task_type("trsm",
                writes=lambda i, k: ("L", i, k),
                reads=lambda i, k: [("A", i, k), ("L", k, k)])
    g.task_type("syrk",
                writes=lambda k, i: ("A", i, i),
                reads=lambda k, i: [("A", i, i), ("L", i, k)])
    g.task_type("gemm",
                writes=lambda k, i, j: ("A", i, j),
                reads=lambda k, i, j: [("A", i, j), ("L", i, k), ("L", j, k)])

    def program():
        # the right-looking factorization's sequential order: the access
        # scan over this order reproduces Fig 8's PTG edge-for-edge
        for k in range(nb):
            yield ("potrf", k)
            for i in range(k + 1, nb):
                yield ("trsm", i, k)
            for i in range(k + 1, nb):
                yield ("syrk", k, i)
            for i in range(k + 1, nb):
                for j in range(k + 1, i):
                    yield ("gemm", k, i, j)

    def res(lo: int, hi: int, p: int, r: int):
        """Indices in [lo, hi) congruent to r mod p."""
        return range(lo + (r - lo) % p, hi, p)

    def owned(shard):
        # the triangular space partitions by block-cyclic residue: each
        # task type's written block fixes a (row mod pr, col mod pc)
        # residue class, so the shard walks only its own rows/columns —
        # O(owned) instead of the O(nb³) full triangle
        r0, c0 = divmod(shard, pc)
        for k in range(nb):
            if k % pr == r0 and k % pc == c0:
                yield ("potrf", k)                       # writes L_kk
            if k % pc == c0:
                for i in res(k + 1, nb, pr, r0):
                    yield ("trsm", i, k)                 # writes L_ik
            for i in res(k + 1, nb, pr, r0):
                if i % pc == c0:
                    yield ("syrk", k, i)                 # writes A_ii
            for i in res(k + 1, nb, pr, r0):
                for j in res(k + 1, i, pc, c0):
                    yield ("gemm", k, i, j)              # writes A_ij

    n_tasks = (nb + 2 * (nb * (nb - 1) // 2)
               + nb * (nb - 1) * (nb - 2) // 6)
    g.sequence(IndexSpace(program, owned, size=n_tasks))
    return g


def cholesky_spec(nb: int, pr: int, pc: int, b: int,
                  dtype=jnp.float32, *, lazy: bool = True) -> BlockPTGSpec:
    """Spec via lazy per-shard derivation by default; ``lazy=False`` is the
    eager global-scan oracle (identical program either way)."""
    return cholesky_graph(nb, pr, pc, b, dtype=dtype).to_block_spec(lazy=lazy)


def cholesky_program(nb: int, pr: int, pc: int, b: int,
                     dtype=jnp.float32) -> BlockProgram:
    """Discover + lower the Cholesky PTG onto the shared comm-planning
    layer. Its panel broadcasts (potrf -> column trsms, trsm -> trailing
    updates) activate only O(grid) of the n² shard pairs per wavefront, so
    the classified plan lowers them to ppermute rounds — the wire carries
    ~10x less padding than the dense all_to_all (see comm_stats)."""
    return build_block_program(cholesky_spec(nb, pr, pc, b, dtype=dtype))


def cholesky_executor(prog: BlockProgram, mesh, axis: str = "shards", *,
                      matmul=None, trsm=None, unroll_cap: int = 64,
                      **policy):
    """Sparsity-aware Cholesky executor with compute/comm overlap: wavefront
    w's panel broadcast is issued before w+1's halo-independent trailing
    updates (owner-local A_ij accumulations), the paper's Fig 9 overlap.
    ``policy`` kwargs (``comm``/``overlap``/``segment_cap``/
    ``density_threshold``) pass through to ``auto_executor``, whose ladder
    is: unrolled below ``unroll_cap``; segmented scan when the exact comm
    signatures form few runs; **union-cover scan** when they fragment (deep
    Cholesky's panel broadcasts change shape every panel) but the union
    permutation cover's wire still beats the dense scan's; the pure dense
    scan only as the loudly-reported last resort. ``matmul``/``trsm`` are
    pluggable bodies — pass e.g. ``repro.kernels.block_gemm.ops.task_matmul``
    to run the trailing updates as a fused Pallas kernel per wavefront
    (the jnp default stays the numerical oracle)."""
    return prog.auto_executor(cholesky_bodies(matmul, trsm), mesh, axis,
                              unroll_cap=unroll_cap, **policy)


def cholesky_bodies(matmul=None, trsm=None) -> Dict[str, object]:
    """Per-block bodies; matmul/trsm pluggable (jnp fallback or Pallas).

    Where ``matmul`` has an ``indexed_update(local, table)`` (``out[t] =
    local[c] - local[a] @ local[b].T`` for rows (c, a, b), as
    ``task_matmul`` has), ``syrk`` and ``gemm`` carry it as ``from_slots
    (local, ops_idx)``: the executor then hands them the slot array and
    their operand columns, and gathers nothing for them."""
    mm = matmul if matmul is not None else lambda a, b: a @ b

    def _trsm(a, l_kk):
        # Solve X · L_kkᵀ = A_ik  =>  X = A_ik · L_kk^{-T}
        return jax.scipy.linalg.solve_triangular(
            l_kk, a.T, lower=True, trans="N").T

    def syrk(a, l):
        return a - mm(l, l.T)

    def gemm(a, li, lj):
        return a - mm(li, lj.T)

    update = getattr(matmul, "indexed_update", None)
    if update is not None:
        # syrk's columns (A_ii, L_ik) as the update's (c, a, b) = (A, L, L)
        syrk.from_slots = lambda local, ops: update(
            local, jnp.concatenate([ops, ops[:, 1:]], axis=1))
        gemm.from_slots = update            # (A_ij, L_ik, L_jk) already

    return {
        "potrf": lambda a: jnp.linalg.cholesky(a),
        "trsm": trsm if trsm is not None else _trsm,
        "syrk": syrk,
        "gemm": gemm,
    }


def cholesky_bodies_numpy() -> Dict[str, object]:
    """Fork-safe pure-numpy bodies. The ``multiproc`` transport forks one
    process per rank; calling into an inherited XLA runtime from a forked
    child can deadlock, so cross-process runs use these. Bit-identity
    across transports holds when both sides run the *same* body set."""
    import scipy.linalg as sla

    def _trsm(a, l_kk):
        return sla.solve_triangular(l_kk, a.T, lower=True, trans="N").T

    return {
        "potrf": lambda a: np.linalg.cholesky(a),
        "trsm": _trsm,
        "syrk": lambda a, l: a - l @ l.T,
        "gemm": lambda a, li, lj: a - li @ lj.T,
    }


def make_spd_blocks(nb: int, b: int, seed: int = 0) -> Dict[Tuple, np.ndarray]:
    """Random SPD matrix, returned as lower-triangle blocks {("A", i, j)}."""
    rng = np.random.default_rng(seed)
    n = nb * b
    m = rng.standard_normal((n, n)).astype(np.float32)
    a = (m @ m.T) / n + np.eye(n, dtype=np.float32) * 2.0
    blocks: Dict[Tuple, np.ndarray] = {}
    for i in range(nb):
        for j in range(i + 1):
            blocks[("A", i, j)] = a[i * b:(i + 1) * b, j * b:(j + 1) * b].copy()
    return blocks, a


def assemble_lower(blocks: Dict[Tuple, np.ndarray], nb: int, b: int):
    """Assemble L from ("L", i, k) blocks (strict upper ignored)."""
    out = np.zeros((nb * b, nb * b), np.float32)
    for i in range(nb):
        for k in range(i + 1):
            blk = blocks.get(("L", i, k))
            if blk is not None:
                out[i * b:(i + 1) * b, k * b:(k + 1) * b] = blk
    out[np.triu_indices(nb * b, 1)] = 0.0
    return out
