"""Tiled dense Cholesky through the compiled task-graph executor.

The system under test is ``cholesky_graph(nb, pr, pc, b).to_program()`` run
by ``cholesky_executor(prog, mesh, matmul=task_matmul)``: discovery and the
slot tables, the executor's lowering, the exchange between chips, the
Pallas ``block_gemm`` kernel for the trailing updates, and the XLA
``potrf``/``trsm`` bodies.

The user's matrix is A = I + U U^T / r, U [N, r] standard normal from the
seed (``spd_rank`` r in the configuration). It is SPD with lambda_min >= 1
and off-diagonal entries ~ r^-1/2, so a wrong block moves the factor far
more than rounding does. The user holds it as ScaLAPACK would: each chip
holds the lower blocks (i, j) it owns on the grid, made there from the
seed. A solve is what the user runs: the blocks go into the program's slots
(the pack, on each chip, no exchange), the executor runs on them (the
packed input is donated), and the solve ends in ``block_until_ready``.

``check`` compares the last timed solve's factor, read out of the program's
slots through its own ``slot_of``, with ``references/cholesky.py`` on the
same blocks, on the first chip, once the program's state is freed.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.block_gemm.ops import task_matmul
from repro.linalg.cholesky import cholesky_executor, cholesky_graph

from bench.references import cholesky as reference

# The kernel whose events and roofline the per-layer metrics read: the HLO
# custom calls that JAX names after ``kernels.block_gemm``'s jitted
# ``block_gemm`` (``vmap_jit_block_gemm__.<n>`` in the executor).
KERNEL = "block_gemm"


def is_kernel(op) -> bool:
    """Whether a device operation (``devtrace.HloOp``) is the kernel."""
    return op.opcode == "custom-call" and KERNEL in op.name


def seed_key(seed: int):
    """A PRNG key for any seed that fits in 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def owned_lower_blocks(nb: int, pr: int, pc: int) -> np.ndarray:
    """[n_shards, K, 2]: the lower blocks (i, j), i >= j, that each chip of
    the pr x pc block-cyclic grid owns, in row-major order; rows past a
    chip's count are padding (i = nb)."""
    per = [[] for _ in range(pr * pc)]
    for i in range(nb):
        for j in range(i + 1):
            per[(i % pr) * pc + (j % pc)].append((i, j))
    k = max(len(p) for p in per)
    out = np.zeros((pr * pc, k, 2), np.int32)
    out[:, :, 0] = nb
    for s, p in enumerate(per):
        out[s, : len(p)] = p
    return out


def spd_blocks(key, coords, nb: int, b: int, r: int):
    """The blocks at ``coords`` ([S, K, 2]) of A = I + U U^T / r; padding
    coordinates (i = nb) give garbage that nothing reads."""
    u = jax.random.normal(key, (nb * b, r), jnp.float32).reshape(nb, b, r)
    i = jnp.minimum(coords[..., 0], nb - 1)
    j = coords[..., 1]
    blk = jnp.einsum("skar,skcr->skac", u[i], u[j],
                     precision=jax.lax.Precision.HIGHEST) / r
    eye = jnp.eye(b, dtype=blk.dtype)
    return blk + jnp.where((i == j)[..., None, None], eye, 0)


def slot_gather(prog, coords):
    """(idx, mask), each [n_shards, n_slots]: slot (s, t) of the program
    holds the user's block ``idx[s, t]`` of chip s (an ``A`` block) where
    ``mask``, zero elsewhere (halo, trash, outputs)."""
    where = {(int(i), int(j)): (s, k) for s in range(coords.shape[0])
             for k, (i, j) in enumerate(coords[s])}
    idx = np.zeros((prog.spec.n_shards, prog.n_slots), np.int32)
    mask = np.zeros_like(idx, bool)
    for blk, (s, slot) in prog.slot_of.items():
        if blk[0] == "A":
            s_user, k = where[(blk[1], blk[2])]
            if s_user != s:
                raise ValueError(f"block {blk} is owned by chip {s_user} "
                                 f"but the program keeps it on chip {s}")
            idx[s, slot], mask[s, slot] = k, True
    return idx, mask


def factor_slots(prog, nb: int):
    """(slots [n_shards, K], coords [n_shards, K, 2]): where each chip's
    L blocks sit in the program's output, through its own ``slot_of``;
    padding has i = nb and reads slot 0."""
    per = [[] for _ in range(prog.spec.n_shards)]
    for blk, (s, slot) in sorted(prog.slot_of.items()):
        if blk[0] == "L":
            per[s].append((slot, blk[1], blk[2]))
    k = max(len(p) for p in per)
    slots = np.zeros((len(per), k), np.int32)
    coords = np.zeros((len(per), k, 2), np.int32)
    coords[:, :, 0] = nb
    for s, p in enumerate(per):
        for t, (slot, i, j) in enumerate(p):
            slots[s, t], coords[s, t] = slot, (i, j)
    return slots, coords


def task_counts(nb: int) -> dict:
    """Tasks of each type in the nb x nb tiled factorisation."""
    return {"potrf": nb, "trsm": nb * (nb - 1) // 2,
            "syrk": nb * (nb - 1) // 2,
            "gemm": nb * (nb - 1) * (nb - 2) // 6}


def task_flops(b: int) -> dict:
    """The algorithm's flops per task of block width b (LAPACK's counts:
    potrf b^3/3, trsm b^3, the symmetric syrk b^3, gemm 2 b^3)."""
    return {"potrf": b ** 3 / 3, "trsm": b ** 3, "syrk": b ** 3,
            "gemm": 2 * b ** 3}


def solve_flops(config: dict) -> float:
    """N^3 / 3: the factorisation's flops, whatever implements it."""
    return (config["nb"] * config["b"]) ** 3 / 3


def kernel_cost(out_shape, operand_shapes, itemsize: int = 4):
    """(flops, bytes) of one ``block_gemm`` call: C[..., m, n] = A[..., m, k]
    B[..., k, n], every operand read once and C written once (the least
    traffic any tiling can have)."""
    *batch, m, n = out_shape
    k = operand_shapes[0][-1]
    t = int(np.prod(batch, dtype=np.int64)) if batch else 1
    elems = sum(int(np.prod(s, dtype=np.int64)) for s in operand_shapes)
    return 2.0 * t * m * n * k, float(itemsize * (elems + t * m * n))


class Cell:
    """One configuration on ``devices``, compiled and ready to solve."""

    def __init__(self, config: dict, devices, seed: int):
        nb, b, r = config["nb"], config["b"], config["spd_rank"]
        pr, pc = config["grid"]
        if len(devices) != pr * pc:
            raise ValueError(f"grid {pr}x{pc} needs {pr * pc} chips, "
                             f"got {len(devices)}")
        self.config = config
        self.devices = list(devices)

        t0 = time.perf_counter()
        prog = cholesky_graph(nb, pr, pc, b).to_program()
        self.host_setup_s = time.perf_counter() - t0

        mesh = Mesh(np.array(self.devices), ("shards",))
        sharded = NamedSharding(mesh, P("shards"))
        put = lambda x: jax.device_put(x, sharded)  # noqa: E731
        t0 = time.perf_counter()
        self.coords = owned_lower_blocks(nb, pr, pc)
        self.user = jax.jit(spd_blocks, static_argnums=(2, 3, 4),
                            out_shardings=sharded)(
            seed_key(seed), put(self.coords), nb, b, r)
        self.user.block_until_ready()
        inputs_s = time.perf_counter() - t0
        idx, mask = slot_gather(prog, self.coords)
        self.idx, self.mask = put(idx), put(mask)
        slots, self.l_coords = factor_slots(prog, nb)
        self.l_slots = put(slots)

        def pack(user, idx, mask):
            got = jax.vmap(lambda u, i: u[i])(user, idx)
            return jnp.where(mask[..., None, None], got, 0)

        self._pack = jax.jit(pack, out_shardings=sharded)
        self._fingerprint = jax.jit(
            lambda out: jnp.sum(jax.lax.bitcast_convert_type(out, jnp.uint32),
                                axis=(1, 2, 3), dtype=jnp.uint32),
            out_shardings=sharded)
        self._factor = jax.jit(lambda out, slots: jax.vmap(
            lambda o, s: o[s])(out, slots), out_shardings=sharded)

        shape = jax.ShapeDtypeStruct((pr * pc, prog.n_slots, b, b),
                                     jnp.float32, sharding=sharded)
        t0 = time.perf_counter()
        with jax.default_matmul_precision(config["matmul_precision"]):
            lowered = jax.jit(
                cholesky_executor(prog, mesh, matmul=task_matmul),
                donate_argnums=0).lower(shape)
        self.lower_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.compiled = lowered.compile()
        self.phases = {"to_program_s": self.host_setup_s,
                       "inputs_s": inputs_s, "lower_s": self.lower_s,
                       "compile_s": time.perf_counter() - t0}
        self.last = None

    def solve(self):
        """One whole solve; returns its output's fingerprint (on the device,
        not waited for)."""
        self.last = None
        with jax.profiler.TraceAnnotation("bench.pack"):
            packed = self._pack(self.user, self.idx, self.mask)
        with jax.profiler.TraceAnnotation("bench.execute"):
            out = self.compiled(packed)
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            out.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.fingerprint"):
            fp = self._fingerprint(out)
        self.last = out
        return fp

    def flops(self) -> float:
        return solve_flops(self.config)

    def check(self) -> dict:
        """Frees the program's state and compares the last solve's factor
        with the reference; returns the numbers compared."""
        first = self.devices[0]
        l_prog = self._factor(self.last, self.l_slots)
        self.last = self.compiled = self.idx = self.mask = None
        l_prog = jax.device_put(l_prog, first)
        user = jax.device_put(self.user, first)
        self.user = None
        return compare(self.config, user, self.coords, l_prog, self.l_coords)


def _flat(coords):
    return (jnp.asarray(coords[..., 0].reshape(-1)),
            jnp.asarray(coords[..., 1].reshape(-1)))


def compare(config: dict, user, coords, l_blocks, l_coords) -> dict:
    """The numbers compared: ``l_err``, max |L - L_ref| / max |L_ref| over
    the blocks ``l_blocks`` at ``l_coords``, L_ref the reference's factor of
    the user's blocks ``user`` at ``coords``, all on one chip."""
    b = config["b"]
    a = reference.assemble(user.reshape(-1, b, b), *_flat(coords),
                           config["nb"])
    del user
    l_ref = reference.cholesky_blocks(a, config["matmul_precision"])
    err = reference.max_rel_err(l_blocks.reshape(-1, b, b),
                                *_flat(l_coords), l_ref)
    return {"l_err": float(err)}


def control(config: dict, devices, seed: int) -> dict:
    """The numbers ``check`` compares, with the reference computed one
    precision below the configuration's in the program's place."""
    one = SingleDeviceSharding(devices[0])
    nb, b = config["nb"], config["b"]
    coords = owned_lower_blocks(nb, *config["grid"])
    user = jax.jit(spd_blocks, static_argnums=(2, 3, 4), out_shardings=one)(
        seed_key(seed), jax.device_put(coords, one), nb, b,
        config["spd_rank"])
    a = reference.assemble(user.reshape(-1, b, b), *_flat(coords), nb)
    l_ctrl = reference.cholesky_blocks(
        a, reference.BELOW[config["matmul_precision"]])
    rows, cols = _flat(coords)
    l_blocks = l_ctrl[jnp.minimum(rows, nb - 1), cols]
    del l_ctrl
    return compare(config, user, coords, l_blocks, coords)
