"""Distributed block GEMM, C = A B, through the compiled task-graph executor.

The system under test is ``gemm_2d_graph(nb, pr, pc, b).to_program()`` run
by ``gemm_executor(prog, mesh, matmul=task_matmul)`` (the program's
``auto_executor``): the ``repro.ptg`` derivation of the k-chain on each C
block, discovery and the slot tables, the executor's lowering with its
operand gather (the gemm body has no indexed form), the vmapped Pallas
``block_gemm`` kernel, and on a grid the exchange of the A and B blocks.

The user's A and B are N x N, standard normal from the seed: each b x b
block is drawn from its own key, so a block is the same whichever chip makes
it. The user holds them as ScaLAPACK would: each chip holds the A and B
blocks it owns on the grid, made there. A solve is what the user runs: the
blocks go into the program's slots (the pack, on each chip, no exchange; the
C slots start at zero), the executor runs on them (the packed input is
donated), and the solve ends in ``block_until_ready``.

``check`` compares the last timed solve's C, read out of the program's slots
through its own ``slot_of``, with ``references/gemm.py`` on the same blocks,
on the first chip, once the program's state is freed.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.block_gemm.ops import task_matmul
from repro.linalg.gemm import gemm_2d_graph, gemm_executor

# the kernel is the Cholesky cells' ``block_gemm``, here in its vmapped form
# (``vmap_jit_block_gemm__.<n>``), found and costed the same way
from bench.families.cholesky import (is_kernel, kernel_cost,  # noqa: F401
                                     seed_key)
from bench.references import gemm as reference
from bench.references.cholesky import assemble, max_rel_err

KINDS = ("A", "B")


def owned_blocks(nb: int, pr: int, pc: int) -> np.ndarray:
    """[n_shards, K, 3]: the A and B blocks (kind, i, j), kind the index in
    ``KINDS``, that each chip of the pr x pc block-cyclic grid owns, A's
    then B's, each in row-major order; rows past a chip's count are padding
    (i = nb)."""
    per = [[] for _ in range(pr * pc)]
    for kind in range(len(KINDS)):
        for i in range(nb):
            for j in range(nb):
                per[(i % pr) * pc + (j % pc)].append((kind, i, j))
    k = max(len(p) for p in per)
    out = np.zeros((pr * pc, k, 3), np.int32)
    out[:, :, 1] = nb
    for s, p in enumerate(per):
        out[s, : len(p)] = p
    return out


def normal_blocks(key, coords, nb: int, b: int):
    """The blocks at ``coords`` ([S, K, 3]), each standard normal from the
    key folded with its own (kind, i, j); padding gives blocks that nothing
    reads."""
    ids = (coords[..., 0] * (nb + 1) + coords[..., 1]) * nb + coords[..., 2]
    draw = jax.vmap(lambda i: jax.random.normal(
        jax.random.fold_in(key, i), (b, b), jnp.float32))
    return jax.vmap(draw)(ids)


def slot_gather(prog, coords):
    """(idx, mask), each [n_shards, n_slots]: slot (s, t) of the program
    holds the user's block ``idx[s, t]`` of chip s (an A or B block) where
    ``mask``, zero elsewhere (C, halo, trash)."""
    where = {(KINDS[kind], int(i), int(j)): (s, k)
             for s in range(coords.shape[0])
             for k, (kind, i, j) in enumerate(coords[s])}
    idx = np.zeros((prog.spec.n_shards, prog.n_slots), np.int32)
    mask = np.zeros_like(idx, bool)
    for blk, (s, slot) in prog.slot_of.items():
        if blk[0] in KINDS:
            s_user, k = where[blk]
            if s_user != s:
                raise ValueError(f"block {blk} is owned by chip {s_user} "
                                 f"but the program keeps it on chip {s}")
            idx[s, slot], mask[s, slot] = k, True
    return idx, mask


def product_slots(prog, nb: int):
    """(slots [n_shards, K], coords [n_shards, K, 2]): where each chip's C
    blocks sit in the program's output, through its own ``slot_of``;
    padding has i = nb and reads slot 0."""
    per = [[] for _ in range(prog.spec.n_shards)]
    for blk, (s, slot) in sorted(prog.slot_of.items()):
        if blk[0] == "C":
            per[s].append((slot, blk[1], blk[2]))
    k = max(len(p) for p in per)
    slots = np.zeros((len(per), k), np.int32)
    coords = np.zeros((len(per), k, 2), np.int32)
    coords[:, :, 0] = nb
    for s, p in enumerate(per):
        for t, (slot, i, j) in enumerate(p):
            slots[s, t], coords[s, t] = slot, (i, j)
    return slots, coords


def solve_flops(config: dict) -> float:
    """2 N^3: the product's flops, whatever implements it."""
    return 2.0 * (config["nb"] * config["b"]) ** 3


class Cell:
    """One configuration on ``devices``, compiled and ready to solve."""

    def __init__(self, config: dict, devices, seed: int):
        nb, b = config["nb"], config["b"]
        pr, pc = config["grid"]
        if len(devices) != pr * pc:
            raise ValueError(f"grid {pr}x{pc} needs {pr * pc} chips, "
                             f"got {len(devices)}")
        self.config = config
        self.devices = list(devices)

        t0 = time.perf_counter()
        prog = gemm_2d_graph(nb, pr, pc, b).to_program()
        self.host_setup_s = time.perf_counter() - t0

        mesh = Mesh(np.array(self.devices), ("shards",))
        sharded = NamedSharding(mesh, P("shards"))
        put = lambda x: jax.device_put(x, sharded)  # noqa: E731
        t0 = time.perf_counter()
        self.coords = owned_blocks(nb, pr, pc)
        self.user = jax.jit(normal_blocks, static_argnums=(2, 3),
                            out_shardings=sharded)(
            seed_key(seed), put(self.coords), nb, b)
        self.user.block_until_ready()
        inputs_s = time.perf_counter() - t0
        idx, mask = slot_gather(prog, self.coords)
        self.idx, self.mask = put(idx), put(mask)
        slots, self.c_coords = product_slots(prog, nb)
        self.c_slots = put(slots)

        def pack(user, idx, mask):
            got = jax.vmap(lambda u, i: u[i])(user, idx)
            return jnp.where(mask[..., None, None], got, 0)

        self._pack = jax.jit(pack, out_shardings=sharded)
        self._fingerprint = jax.jit(
            lambda out: jnp.sum(jax.lax.bitcast_convert_type(out, jnp.uint32),
                                axis=(1, 2, 3), dtype=jnp.uint32),
            out_shardings=sharded)
        self._product = jax.jit(lambda out, slots: jax.vmap(
            lambda o, s: o[s])(out, slots), out_shardings=sharded)

        shape = jax.ShapeDtypeStruct((pr * pc, prog.n_slots, b, b),
                                     jnp.float32, sharding=sharded)
        t0 = time.perf_counter()
        with jax.default_matmul_precision(config["matmul_precision"]):
            lowered = jax.jit(gemm_executor(prog, mesh, matmul=task_matmul),
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
        """Frees the program's state and compares the last solve's C with
        the reference; returns the numbers compared."""
        first = self.devices[0]
        c_prog = self._product(self.last, self.c_slots)
        self.last = self.compiled = self.idx = self.mask = None
        c_prog = jax.device_put(c_prog, first)
        user = jax.device_put(self.user, first)
        self.user = None
        return compare(self.config, user, self.coords, c_prog, self.c_coords)


def _operands(config: dict, user, coords):
    """A and B in block layout ([nb, nb, b, b] each), from the user's blocks
    ``user`` at ``coords``, on one chip."""
    nb, b = config["nb"], config["b"]
    flat = user.reshape(-1, b, b)
    kind, i, j = (jnp.asarray(coords[..., d].reshape(-1)) for d in range(3))
    return tuple(assemble(flat, jnp.where(kind == k, i, nb), j, nb)
                 for k in range(len(KINDS)))


def compare(config: dict, user, coords, c_blocks, c_coords) -> dict:
    """The numbers compared: ``c_err``, max |C - C_ref| / max |C_ref| over
    the blocks ``c_blocks`` at ``c_coords``, C_ref the reference's product
    of the user's blocks ``user`` at ``coords``, all on one chip."""
    a, b_ = _operands(config, user, coords)
    del user
    c_ref = reference.gemm_blocks(a, b_, config["matmul_precision"])
    del a, b_
    err = max_rel_err(c_blocks.reshape(-1, *c_blocks.shape[-2:]),
                      jnp.asarray(c_coords[..., 0].reshape(-1)),
                      jnp.asarray(c_coords[..., 1].reshape(-1)), c_ref)
    return {"c_err": float(err)}


def control(config: dict, devices, seed: int) -> dict:
    """The numbers ``check`` compares, with the reference computed one
    precision below the configuration's in the program's place."""
    one = SingleDeviceSharding(devices[0])
    nb, b = config["nb"], config["b"]
    coords = owned_blocks(nb, *config["grid"])
    user = jax.jit(normal_blocks, static_argnums=(2, 3), out_shardings=one)(
        seed_key(seed), jax.device_put(coords, one), nb, b)
    a, b_ = _operands(config, user, coords)
    c_ctrl = reference.gemm_blocks(
        a, b_, reference.BELOW[config["matmul_precision"]])
    del a, b_
    c_coords = np.stack(np.meshgrid(np.arange(nb), np.arange(nb),
                                    indexing="ij"), -1).reshape(1, -1, 2)
    return compare(config, user, coords, c_ctrl.reshape(-1, b, b), c_coords)
