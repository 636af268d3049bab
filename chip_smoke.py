"""Smoke run of the compiled task-graph executor on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # 2x2-sharded Cholesky, four chips

One process, no children. The one-chip run has four phases, in order:

  device    jax version, platform, device kind and count; anything but a TPU
            exits non-zero before a result is printed.
  cholesky  ``cholesky_graph(24, 1, 1, 1024).to_program()`` (N = 24576, f32)
            through ``cholesky_executor`` with Pallas ``task_matmul`` bodies,
            the packed input donated. Checks that the compiled program holds
            the Mosaic kernel (``tpu_custom_call``), the backward residual
            max|LL^T - A| / max|A| computed on the device by row blocks, and
            the factor against ``jnp.linalg.cholesky``.
  gemm      ``gemm_2d_graph(16, 1, 1, 1024)`` (N = 16384) with ``task_matmul``
            bodies against ``jnp.dot``.
  stream    the host runtime (``Graph.run_host``, inproc transport) and a
            ``SchedulerService(n_shards=2)`` serving two clients, both with jnp
            bodies on the chip, at nb = 8; the stream must be bit-identical to
            ``run_host`` and both must agree with the compiled executor.

``--four-chips`` runs only ``cholesky_graph(16, 2, 2, 1024)`` on a 4-device
mesh against the same graph on one shard of ``devices[0]``, and checks that
every device holds its shard.

Sizes come from compiling for a described v5e (``memory_analysis()``):
Cholesky nb 24 needs 2.52 GB of arguments, 2.52 GB of output (aliased to the
donated input) and 6.37 GB of temporaries; nb 32 would need 4.43 + 4.43 +
11.70 GB, more than the chip's 16 GB.

Numerics. Every matmul runs at ``Precision.HIGHEST`` (f32, set globally below),
so the bounds come from the f32 unit roundoff u = 2^-24:

  residual  max|LL^T - A| / max|A| <= gamma(N + 1) = (N+1)u / (1 - (N+1)u),
            Cholesky's backward error bound |dA| <= gamma(N+1)|L||L^T|, with
            (|L||L^T|)_ij <= sqrt(a_ii a_jj) <= max|A| for SPD A.
  factor    max|L - L_ref| / max|L_ref| <= kappa_2(A) * gamma(N + 1): the
            forward error of two backward-stable factors of A.
  gemm      max|C - C_ref| <= 2 gamma(N) max(|A||B|).

The SPD input is A = I + U U^T / r with U [N, r] standard normal from
``--seed`` and r = 512, built on the device in O(N^2 r): lambda_min(A) >= 1
(= 1 when N > r), so kappa_2(A) <= 1 + lambda_max(U^T U) / r, an r x r
eigenproblem.
Its off-diagonal entries are ~r^-1/2 against a diagonal of ~2, so a wrong
block moves the residual by ~1e-2, far above the bound; a strongly
diagonally dominant A would hide such a block under its diagonal.

Earlier lines are one JSON object per phase, with compile and wall-clock
seconds; the last line is ``{"ok": true, "device": {...}}``. A phase that
overruns its timeout kills the run with a traceback (``faulthandler``).
"""

from __future__ import annotations

import argparse
import faulthandler
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.kernels.block_gemm.ops import task_matmul  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.linalg.cholesky import (assemble_lower,  # noqa: E402
                                   cholesky_bodies, cholesky_executor,
                                   cholesky_graph)
from repro.linalg.gemm import gemm_2d_graph, gemm_executor  # noqa: E402
from repro.sched import SchedulerService  # noqa: E402

B = 1024            # block width: a multiple of the 128x128 MXU
CHOL_NB = 24        # N = 24576
GEMM_NB = 16        # N = 16384
STREAM_NB = 8       # N = 8192
FOUR_NB = 16        # N = 16384 on the 2x2 grid
SPD_RANK = 512
U_F32 = 2.0 ** -24
TIMEOUT_S = {"cholesky": 600, "gemm": 300, "stream": 600, "four_chips": 900}


def gamma(n: int) -> float:
    return n * U_F32 / (1 - n * U_F32)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def run_phase(name: str, fn, *args) -> dict:
    """Run one phase under a watchdog that dumps every thread's stack and
    exits the process if it overruns ``TIMEOUT_S[name]`` (a hang fails the
    run), then print its fields and wall-clock seconds."""
    faulthandler.dump_traceback_later(TIMEOUT_S[name], exit=True)
    t0 = time.perf_counter()
    try:
        fields = fn(*args)
    finally:
        faulthandler.cancel_dump_traceback_later()
    emit(phase=name, wall_s=time.perf_counter() - t0, **fields)
    return fields


def one_shard_mesh(device) -> Mesh:
    return Mesh(np.array([device]), ("shards",))


def spd_factor(seed: int, n: int):
    """U of A = I + U U^T / r, and the bound on kappa_2(A)."""
    u = jax.random.normal(jax.random.key(seed), (n, SPD_RANK), jnp.float32)
    kappa = 1.0 + float(jnp.linalg.eigvalsh(u.T @ u)[-1]) / SPD_RANK
    return u, kappa


@jax.jit
def spd_dense(u):
    return jnp.eye(u.shape[0], dtype=u.dtype) + u @ u.T / SPD_RANK


def to_blocks(dense, b: int):
    """[nb*b, nb*b] -> [nb, nb, b, b]."""
    nb = dense.shape[0] // b
    return dense.reshape(nb, b, nb, b).transpose(0, 2, 1, 3)


def pack_on_device(prog, sources, sharding):
    """``prog.pack`` on the device: each (shard, slot) whose block is
    ``(kind, i, j)`` with ``kind`` in ``sources`` ([nb, nb, b, b] arrays)
    gets that block; every other slot (halo, trash, outputs) is zero."""
    kinds = sorted(sources)
    nb, _, b0, b1 = sources[kinds[0]].shape
    zero = len(kinds) * nb * nb
    idx = np.full((prog.spec.n_shards, prog.n_slots), zero, np.int32)
    for blk, (s, slot) in prog.slot_of.items():
        if blk[0] in sources:
            idx[s, slot] = kinds.index(blk[0]) * nb * nb + blk[1] * nb + blk[2]

    def gather(*srcs):
        flat = jnp.concatenate([x.reshape(-1, b0, b1) for x in srcs]
                               + [jnp.zeros((1, b0, b1), srcs[0].dtype)])
        return flat[idx]

    return jax.jit(gather, out_shardings=sharding)(
        *(sources[k] for k in kinds))


def dense_from_packed(prog, out, kind: str, nb: int):
    """The [nb*b, nb*b] matrix of ``out``'s ``(kind, i, j)`` blocks (zero
    where the program holds no such block), gathered on the device."""
    b0, b1 = prog.spec.block_shape
    idx = np.zeros((nb, nb), np.int32)
    mask = np.zeros((nb, nb), bool)
    for blk, (s, slot) in prog.slot_of.items():
        if blk[0] == kind:
            idx[blk[1], blk[2]] = s * prog.n_slots + slot
            mask[blk[1], blk[2]] = True

    @jax.jit
    def gather(packed):
        blocks = packed.reshape(-1, b0, b1)[idx]
        blocks = jnp.where(mask[:, :, None, None], blocks, 0)
        return blocks.transpose(0, 2, 1, 3).reshape(nb * b0, nb * b1)

    return gather(out)


@functools.partial(jax.jit, static_argnums=2)
def cholesky_residual(l, u, rows: int):
    """(max|LL^T - A|, max|A|) with A = I + U U^T / r, ``rows`` rows at a
    time so no N x N product is ever materialised."""
    n = l.shape[0]

    def body(i, acc):
        li = jax.lax.dynamic_slice_in_dim(l, i * rows, rows)
        ui = jax.lax.dynamic_slice_in_dim(u, i * rows, rows)
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0) + i * rows
        c = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
        ai = ui @ u.T / SPD_RANK + (r == c).astype(l.dtype)
        err = jnp.max(jnp.abs(li @ l.T - ai))
        return (jnp.maximum(acc[0], err),
                jnp.maximum(acc[1], jnp.max(jnp.abs(ai))))

    zero = jnp.zeros((), l.dtype)
    return jax.lax.fori_loop(0, n // rows, body, (zero, zero))


@jax.jit
def max_rel_diff(x, ref):
    return jnp.max(jnp.abs(x - ref)) / jnp.max(jnp.abs(ref))


def compile_executor(make_executor, x):
    """Lower and compile ``make_executor()`` for ``x`` (donated); returns the
    compiled program and its lower / compile seconds."""
    t0 = time.perf_counter()
    lowered = jax.jit(make_executor(), donate_argnums=0).lower(x)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, t1 - t0, time.perf_counter() - t1


def memory_fields(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"bytes_args": ma.argument_size_in_bytes,
            "bytes_out": ma.output_size_in_bytes,
            "bytes_alias": ma.alias_size_in_bytes,
            "bytes_temp": ma.temp_size_in_bytes}


def cholesky_phase(device, nb: int, b: int, seed: int) -> dict:
    """Factor the seeded SPD matrix on one chip through the compiled
    executor with Pallas bodies; returns the phase's fields."""
    n = nb * b
    t0 = time.perf_counter()
    prog = cholesky_graph(nb, 1, 1, b).to_program()
    mesh = one_shard_mesh(device)
    sharding = NamedSharding(mesh, P("shards"))
    t_lowering = time.perf_counter() - t0

    u, kappa = spd_factor(seed, n)
    packed = pack_on_device(prog, {"A": to_blocks(spd_dense(u), b)},
                            sharding)
    compiled, t_lower, t_compile = compile_executor(
        lambda: cholesky_executor(prog, mesh, matmul=task_matmul), packed)
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(packed))
    t_run = time.perf_counter() - t1

    l = dense_from_packed(prog, out, "L", nb)
    del out
    err, a_max = cholesky_residual(l, u, b)
    residual = float(err / a_max)
    fields = {"N": n, "nb": nb, "b": b, "wavefronts":
              prog.schedule.n_wavefronts, **memory_fields(compiled),
              "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
              "graph_lowering_s": t_lowering, "trace_s": t_lower,
              "compile_s": t_compile, "run_s": t_run,
              "kappa": kappa, "residual": residual,
              "residual_bound": gamma(n + 1)}
    l_ref = jnp.linalg.cholesky(spd_dense(u))
    fields["ref_err"] = float(max_rel_diff(l, l_ref))
    fields["ref_bound"] = kappa * gamma(n + 1)
    return fields


def gemm_phase(device, nb: int, b: int, seed: int) -> dict:
    """C = A B on one chip through the compiled executor with Pallas bodies,
    against ``jnp.dot``."""
    n = nb * b
    prog = gemm_2d_graph(nb, 1, 1, b).to_program()
    mesh = one_shard_mesh(device)
    ka, kb = jax.random.split(jax.random.key(seed))
    a = jax.random.normal(ka, (n, n), jnp.float32)
    bm = jax.random.normal(kb, (n, n), jnp.float32)
    packed = pack_on_device(prog, {"A": to_blocks(a, b),
                                   "B": to_blocks(bm, b)},
                            NamedSharding(mesh, P("shards")))
    compiled, t_lower, t_compile = compile_executor(
        lambda: gemm_executor(prog, mesh, matmul=task_matmul), packed)
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(packed))
    t_run = time.perf_counter() - t1

    c = dense_from_packed(prog, out, "C", nb)
    del out
    err = float(jnp.max(jnp.abs(c - a @ bm)))
    scale = float(jnp.max(jnp.abs(a) @ jnp.abs(bm)))
    return {"N": n, "nb": nb, "b": b, "wavefronts": prog.schedule.n_wavefronts,
            **memory_fields(compiled),
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
            "trace_s": t_lower, "compile_s": t_compile, "run_s": t_run,
            "err_over_abs_ab": err / scale, "bound": 2 * gamma(n)}


def stream_phase(device, nb: int, b: int, seed: int, *, n_threads: int = 2,
                 submissions: int = 2, timeout: float = 300.0) -> dict:
    """The host runtime and the resident stream, jnp bodies on the chip, on
    ``cholesky_graph(nb, 2, 1, b)``; and the compiled executor on the same
    factorisation on one shard."""
    n = nb * b
    u, kappa = spd_factor(seed, n)
    a = np.asarray(spd_dense(u))
    blocks = {("A", i, j): a[i * b:(i + 1) * b, j * b:(j + 1) * b]
              for i in range(nb) for j in range(i + 1)}
    bodies = cholesky_bodies()

    t0 = time.perf_counter()
    host = cholesky_graph(nb, 2, 1, b).run_host(
        blocks, bodies, n_threads=n_threads, timeout=timeout,
        transport="inproc")
    t_host = time.perf_counter() - t0

    t0 = time.perf_counter()
    with SchedulerService(n_shards=2, n_threads=n_threads, timeout=timeout,
                          transport="inproc") as svc:
        clients = [svc.client("alice"), svc.client("bob")]
        futs = [c.submit(cholesky_graph(nb, 2, 1, b), blocks, bodies,
                         namespace=f"{c.name}/{j}")
                for j in range(submissions) for c in clients]
        streamed = [f.result(timeout) for f in futs]
    t_stream = time.perf_counter() - t0
    # a submission returns the blocks its tasks wrote (every L block and
    # the updated A blocks); run_host also hands back untouched inputs
    factor = {k for k in host if k[0] == "L"}
    identical = all(
        factor <= s.keys()
        and all(np.array_equal(s[k], host[k]) for k in s)
        for s in streamed)

    prog = cholesky_graph(nb, 1, 1, b).to_program()
    mesh = one_shard_mesh(device)
    packed = pack_on_device(prog, {"A": to_blocks(jnp.asarray(a), b)},
                            NamedSharding(mesh, P("shards")))
    out = jax.jit(cholesky_executor(prog, mesh, matmul=task_matmul))(packed)
    l_compiled = dense_from_packed(prog, out, "L", nb)
    l_host = jnp.asarray(assemble_lower(host, nb, b))
    return {"N": n, "nb": nb, "b": b, "host_s": t_host, "stream_s": t_stream,
            "submissions": len(streamed),
            "stream_bit_identical_to_run_host": identical,
            "host_vs_compiled": float(max_rel_diff(l_host, l_compiled)),
            "bound": kappa * gamma(n + 1)}


def four_chips_phase(devices, nb: int, b: int, seed: int) -> dict:
    """``cholesky_graph(nb, 2, 2, b)`` sharded over four devices against the
    same factorisation on one shard of ``devices[0]``."""
    n = nb * b
    u, kappa = spd_factor(seed, n)
    a_blocks = to_blocks(spd_dense(u), b)
    runs = {}
    for name, (pr, pc, devs) in {"one_shard": (1, 1, devices[:1]),
                                 "two_by_two": (2, 2, devices[:4])}.items():
        prog = cholesky_graph(nb, pr, pc, b).to_program()
        mesh = Mesh(np.array(devs), ("shards",))
        packed = pack_on_device(prog, {"A": a_blocks},
                                NamedSharding(mesh, P("shards")))
        compiled, t_lower, t_compile = compile_executor(
            lambda: cholesky_executor(prog, mesh, matmul=task_matmul), packed)
        t1 = time.perf_counter()
        out = jax.block_until_ready(compiled(packed))
        runs[name] = (prog, out, {
            "trace_s": t_lower, "compile_s": t_compile,
            "run_s": time.perf_counter() - t1, **memory_fields(compiled),
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text()})

    prog4, out4, _ = runs["two_by_two"]
    shards = {sh.device: np.asarray(sh.data) for sh in out4.addressable_shards}
    # every device holds a shard, and every block a shard owns is written
    # there (nonzero) — not gathered onto the first device
    owned_written = all(
        np.any(shards[devices[s]][0, slot])
        for blk, (s, slot) in prog4.slot_of.items() if blk[0] == "L")
    l1 = np.asarray(dense_from_packed(runs["one_shard"][0],
                                      runs["one_shard"][1], "L", nb))
    l4 = np.asarray(dense_from_packed(prog4, out4, "L", nb))
    diff = float(np.max(np.abs(l4 - l1)) / np.max(np.abs(l1)))
    return {"N": n, "nb": nb, "b": b,
            **{f"{k}_{name}": v for name, (_, _, f) in runs.items()
               for k, v in f.items()},
            "devices_holding_shards": len(shards),
            "owned_blocks_written_on_owner": bool(owned_written),
            "bit_identical": bool(np.array_equal(l1, l4)),
            "max_rel_diff": diff, "bound": kappa * gamma(n + 1)}


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-sharded Cholesky on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = use_compile_cache()
    jax.config.update("jax_default_matmul_precision", "highest")
    devices = jax.devices()
    dev = devices[0]
    emit(phase="device", jax=jax.__version__, platform=dev.platform,
         kind=dev.device_kind, count=len(devices), compile_cache=cache,
         matmul_precision="highest")
    require(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")

    if args.four_chips:
        require(len(devices) >= 4, f"--four-chips needs 4 devices, "
                                   f"have {len(devices)}")
        f = run_phase("four_chips", four_chips_phase, devices, FOUR_NB, B,
                      args.seed)
        require(f["devices_holding_shards"] == 4, "a device holds no shard")
        require(f["owned_blocks_written_on_owner"],
                "an owned block was not written on its owner")
        require(f["tpu_custom_call_two_by_two"], "no Mosaic kernel")
        require(f["max_rel_diff"] <= f["bound"], "2x2 differs from 1 shard")
    else:
        f = run_phase("cholesky", cholesky_phase, dev, CHOL_NB, B, args.seed)
        require(f["tpu_custom_call"], "Cholesky ran without a Mosaic kernel")
        require(f["residual"] <= f["residual_bound"], "Cholesky residual")
        require(f["ref_err"] <= f["ref_bound"], "Cholesky vs jnp reference")

        f = run_phase("gemm", gemm_phase, dev, GEMM_NB, B, args.seed)
        require(f["tpu_custom_call"], "GEMM ran without a Mosaic kernel")
        require(f["err_over_abs_ab"] <= f["bound"], "GEMM vs jnp.dot")

        f = run_phase("stream", stream_phase, dev, STREAM_NB, B, args.seed)
        require(f["stream_bit_identical_to_run_host"],
                "stream differs from run_host")
        require(f["host_vs_compiled"] <= f["bound"],
                "host runtime vs compiled executor")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
