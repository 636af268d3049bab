"""Multi-device validation cases, run in a *subprocess* so the forced host
device count never leaks into the main pytest process (smoke tests and
benches must keep seeing 1 device).

Usage:  python -m tests.multi_device_cases <case> [<case> ...]
Prints "CASE <name> OK" per passing case; non-zero exit on failure.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


class SkipCase(Exception):
    """Raised by a case that cannot run in this process (too few devices);
    main() reports ``CASE <name> SKIP`` and exits 0, and the pytest
    dispatcher in test_ptg_linalg turns that into a pytest skip."""


def _require_devices(n):
    have = len(jax.devices())
    if have < n:
        raise SkipCase(f"needs {n} devices, have {have}")


def _mesh(n):
    _require_devices(n)
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("shards",))


def case_gemm_2d():
    from repro.linalg.gemm import (assemble, gemm_2d_program, gemm_executor,
                                   gemm_bodies, make_blocks)

    for staged in (False, True):
        nb, pr, pc, b = 4, 2, 2, 8
        prog = gemm_2d_program(nb, pr, pc, b, staged=staged)
        blocks = make_blocks(None, nb, b)
        mesh = _mesh(prog.spec.n_shards)
        with mesh:
            run = jax.jit(gemm_executor(prog, mesh))
            out = prog.unpack(run(jnp.asarray(prog.pack(blocks))))
        a = assemble(blocks, "A", nb, b)
        bm = assemble(blocks, "B", nb, b)
        c = assemble(out, "C", nb, b)
        np.testing.assert_allclose(c, a @ bm, rtol=2e-4, atol=2e-4)


def case_gemm_3d():
    from repro.linalg.gemm import (assemble, gemm_3d_program, gemm_executor,
                                   make_blocks)

    nb, q, b = 4, 2, 8
    prog = gemm_3d_program(nb, q, b)
    blocks = make_blocks(None, nb, b, with_partials=tuple(range(q)))
    mesh = _mesh(prog.spec.n_shards)
    with mesh:
        run = jax.jit(gemm_executor(prog, mesh))
        out = prog.unpack(run(jnp.asarray(prog.pack(blocks))))
    a = assemble(blocks, "A", nb, b)
    bm = assemble(blocks, "B", nb, b)
    c = assemble(out, "C", nb, b)
    np.testing.assert_allclose(c, a @ bm, rtol=2e-4, atol=2e-4)


def case_gemm_unrolled_matches_scan():
    from repro.core.schedule import build_block_program
    from repro.linalg.gemm import gemm_2d_spec, gemm_bodies, make_blocks

    nb, pr, pc, b = 3, 2, 2, 4
    spec = gemm_2d_spec(nb, pr, pc, b)
    prog = build_block_program(spec)
    blocks = make_blocks(None, nb, b)
    packed = jnp.asarray(prog.pack(blocks))
    mesh = _mesh(spec.n_shards)
    with mesh:
        out_scan = prog.unpack(jax.jit(prog.executor(
            gemm_bodies(), mesh, scan=True))(packed))
        out_unrl = prog.unpack(jax.jit(prog.executor(
            gemm_bodies(), mesh, scan=False))(packed))
    for key in out_scan:
        np.testing.assert_allclose(out_scan[key], out_unrl[key],
                                   rtol=1e-5, atol=1e-5)


def case_cholesky():
    from repro.linalg.cholesky import (assemble_lower, cholesky_executor,
                                       cholesky_program, make_spd_blocks)

    nb, pr, pc, b = 5, 2, 2, 8
    prog = cholesky_program(nb, pr, pc, b)
    blocks, a = make_spd_blocks(nb, b)
    mesh = _mesh(prog.spec.n_shards)
    with mesh:
        run = jax.jit(cholesky_executor(prog, mesh))
        out = prog.unpack(run(jnp.asarray(prog.pack(blocks))))
    l = assemble_lower(out, nb, b)
    np.testing.assert_allclose(l, np.linalg.cholesky(a), rtol=5e-3, atol=5e-3)


def case_lowering_identity():
    """Every lowering of the same program — dense scan, segmented scan
    (sparse/auto, with and without overlap), unrolled dense, sparse, auto,
    and the double-buffered overlap modes — is bit-identical on GEMM and
    Cholesky (same bodies over the same operand values)."""
    from repro.core.schedule import build_block_program
    from repro.linalg.cholesky import (cholesky_bodies, cholesky_spec,
                                       make_spd_blocks)
    from repro.linalg.gemm import gemm_2d_spec, gemm_bodies, make_blocks

    cases = []
    spec = cholesky_spec(6, 2, 2, 4)
    blocks, _ = make_spd_blocks(6, 4)
    cases.append((spec, cholesky_bodies(), blocks))
    for staged in (False, True):
        spec = gemm_2d_spec(4, 2, 2, 4, staged=staged)
        cases.append((spec, gemm_bodies(), make_blocks(None, 4, 4)))

    variants = (
        dict(scan=True),
        dict(scan=True, comm="sparse"),
        dict(scan=True, comm="auto"),
        dict(scan=True, comm="auto", overlap=True),
        dict(scan=True, comm="sparse", overlap=True),
        dict(scan=True, comm="dense", overlap=True),
        dict(scan=False, comm="sparse"),
        dict(scan=False, comm="auto"),
        dict(scan=False, comm="dense", overlap=True),
        dict(scan=False, comm="sparse", overlap=True),
        dict(scan=False, comm="auto", overlap=True),
    )
    for spec, bodies, blocks in cases:
        prog = build_block_program(spec)
        mesh = _mesh(prog.spec.n_shards)
        packed = jnp.asarray(prog.pack(blocks))
        with mesh:
            ref = np.asarray(jax.jit(prog.executor(
                bodies, mesh, scan=False, comm="dense"))(packed))
            for kw in variants:
                got = np.asarray(jax.jit(prog.executor(
                    bodies, mesh, **kw))(packed))
                # compare real slots only (trash accumulates padded writes)
                for blk, (s, slot) in prog.slot_of.items():
                    np.testing.assert_array_equal(
                        ref[s, slot], got[s, slot], err_msg=f"{kw} {blk}")
                for (s, blk), slot in prog.halo_slot.items():
                    np.testing.assert_array_equal(
                        ref[s, slot], got[s, slot], err_msg=f"{kw} halo {blk}")


def case_cholesky_host_matches_compiled():
    from repro.core.schedule import build_block_program
    from repro.linalg.cholesky import (cholesky_bodies, cholesky_spec,
                                       make_spd_blocks)
    from repro.linalg.host_exec import as_numpy_bodies, run_host_ptg

    nb, pr, pc, b = 4, 2, 2, 4
    spec = cholesky_spec(nb, pr, pc, b)
    blocks, _ = make_spd_blocks(nb, b)
    host = run_host_ptg(spec, blocks, as_numpy_bodies(cholesky_bodies()))
    prog = build_block_program(spec)
    mesh = _mesh(spec.n_shards)
    with mesh:
        run = jax.jit(prog.executor(cholesky_bodies(), mesh))
        comp = prog.unpack(run(jnp.asarray(prog.pack(blocks))))
    for key, arr in host.items():
        if key[0] == "L":
            np.testing.assert_allclose(arr, comp[key], rtol=1e-5, atol=1e-5)




def case_taskbench_identity():
    """Every Task-Bench dependence pattern, executed by the sparse/overlap
    executor, matches the sequential oracle and the dense unrolled
    reference bit-for-bit."""
    from repro.core.schedule import build_block_program
    from benchmarks.taskbench_scaling import (taskbench_blocks,
                                              taskbench_bodies,
                                              taskbench_oracle,
                                              taskbench_spec)

    width, depth, n_shards, b = 8, 6, 4, 4
    mesh = _mesh(n_shards)
    for pattern in ("stencil", "fft", "tree", "random"):
        spec, deps = taskbench_spec(pattern, width, depth, n_shards, b,
                                    fan=2)
        prog = build_block_program(spec)
        blocks = taskbench_blocks(width, depth, b)
        packed = jnp.asarray(prog.pack(blocks))
        bodies = taskbench_bodies()
        with mesh:
            ref = prog.unpack(jax.jit(prog.executor(
                bodies, mesh, scan=False, comm="dense"))(packed))
            got = prog.unpack(jax.jit(prog.auto_executor(
                bodies, mesh))(packed))
        want = taskbench_oracle(blocks, deps, width, depth)
        for blk in want:
            np.testing.assert_allclose(got[blk], want[blk],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"{pattern} {blk}")
            np.testing.assert_array_equal(np.asarray(got[blk]),
                                          np.asarray(ref[blk]),
                                          err_msg=f"{pattern} {blk}")


def case_segmented_identity():
    """The segmented-scan executor is bit-identical to the unrolled
    ``comm="auto"`` reference AND to the pure dense scan across Task-Bench
    dependence patterns x shard counts x depths — including ragged
    boundaries (depth not a multiple of any segment length, single-
    wavefront segments from fft's stride cycling, and random's all-dense
    schedules degenerating to one all_to_all run)."""
    from repro.core.schedule import build_block_program
    from benchmarks.taskbench_scaling import (taskbench_blocks,
                                              taskbench_bodies,
                                              taskbench_spec)

    width, b = 8, 4
    bodies = taskbench_bodies()
    for pattern in ("stencil", "fft", "tree", "random"):
        for n_shards, depth in ((2, 7), (4, 5), (4, 13)):
            mesh = _mesh(n_shards)
            spec, _deps = taskbench_spec(pattern, width, depth, n_shards, b,
                                         fan=2)
            prog = build_block_program(spec)
            segs = prog.segments()
            assert segs[0][0] == 0 and segs[-1][1] == depth
            blocks = taskbench_blocks(width, depth, b)
            packed = jnp.asarray(prog.pack(blocks))
            with mesh:
                ref = np.asarray(jax.jit(prog.executor(
                    bodies, mesh, scan=False, comm="auto"))(packed))
                for kw in (dict(scan=True),                    # dense scan
                           dict(scan=True, comm="auto"),
                           dict(scan=True, comm="auto", overlap=True),
                           dict(scan=True, comm="sparse", overlap=True)):
                    got = np.asarray(jax.jit(prog.executor(
                        bodies, mesh, **kw))(packed))
                    # compare real slots only (trash accumulates padding)
                    for blk, (s, slot) in prog.slot_of.items():
                        np.testing.assert_array_equal(
                            ref[s, slot], got[s, slot],
                            err_msg=f"{pattern}/s{n_shards}/d{depth} "
                                    f"{kw} {blk}")
                    for (s, blk), slot in prog.halo_slot.items():
                        np.testing.assert_array_equal(
                            ref[s, slot], got[s, slot],
                            err_msg=f"{pattern}/s{n_shards}/d{depth} "
                                    f"{kw} halo {blk}")


def case_unified_graph():
    """The one-API story, executed: a single declarative ``repro.ptg``
    Graph (Cholesky) runs on BOTH back-ends — the async host Taskflow
    runtime and the compiled block executor — and agrees with the oracle;
    and the builder-derived program's executor output is bit-identical to
    the frozen legacy hand-written spec's."""
    from repro.core.schedule import build_block_program
    from repro.linalg.cholesky import (assemble_lower, cholesky_bodies,
                                       cholesky_graph, make_spd_blocks)
    from repro.linalg.host_exec import as_numpy_bodies
    from tests.legacy_specs import legacy_cholesky_spec

    nb, pr, pc, b = 4, 2, 2, 4
    graph = cholesky_graph(nb, pr, pc, b)
    blocks, a = make_spd_blocks(nb, b)
    mesh = _mesh(graph.n_shards)

    # lowering (a): host runtime — Taskflow + AM wiring from derived edges
    host = graph.run_host(blocks, as_numpy_bodies(cholesky_bodies()))

    # lowering (b): compiled block executor from the same definition
    prog = graph.to_program(validate=True)
    with mesh:
        run = jax.jit(prog.auto_executor(cholesky_bodies(), mesh))
        comp = prog.unpack(run(jnp.asarray(prog.pack(blocks))))

    l_host = assemble_lower(host, nb, b)
    l_comp = assemble_lower(comp, nb, b)
    want = np.linalg.cholesky(a)
    np.testing.assert_allclose(l_host, l_comp, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l_comp, want, rtol=5e-3, atol=5e-3)

    # and bit-identity vs the pre-redesign hand-written spec's executor
    legacy = build_block_program(legacy_cholesky_spec(nb, pr, pc, b))
    with mesh:
        ref = legacy.unpack(jax.jit(
            legacy.auto_executor(cholesky_bodies(), mesh))(
                jnp.asarray(legacy.pack(blocks))))
    for key, arr in comp.items():
        np.testing.assert_array_equal(np.asarray(arr),
                                      np.asarray(ref[key]), err_msg=str(key))


def case_pallas_bodies():
    """Pallas kernels as task bodies, end to end under the block executor:
    (a) GEMM and Cholesky with ``task_matmul`` (the fused per-wavefront
    ``vmap(pallas_call)`` launch) match the jnp-body lowering within f32
    tolerance, across the unrolled AND scan policies; (b) an attention
    chain runs ``task_attention`` (flash attention re-shaped to the 2D
    block form) against an ``mha_ref``-bodied lowering of the same PTG."""
    from repro.kernels.block_gemm.ops import task_matmul
    from repro.kernels.flash_attention.ops import task_attention
    from repro.kernels.flash_attention.ref import mha_ref
    from repro.linalg.cholesky import (assemble_lower, cholesky_executor,
                                       cholesky_program, make_spd_blocks)
    from repro.linalg.gemm import (assemble, gemm_2d_program, gemm_executor,
                                   make_blocks)
    from repro.ptg import Graph

    nb, pr, pc, b = 4, 2, 2, 8
    mesh = _mesh(pr * pc)

    # (a) GEMM: pallas body vs jnp body, unrolled and forced-scan policies
    prog = gemm_2d_program(nb, pr, pc, b, staged=True)
    blocks = make_blocks(None, nb, b)
    packed = jnp.asarray(prog.pack(blocks))
    a = assemble(blocks, "A", nb, b)
    bm = assemble(blocks, "B", nb, b)
    for policy in ({}, dict(unroll_cap=2)):        # unrolled / segmented scan
        with mesh:
            got = prog.unpack(jax.jit(gemm_executor(
                prog, mesh, matmul=task_matmul, **policy))(packed))
            ref = prog.unpack(jax.jit(gemm_executor(
                prog, mesh, **policy))(packed))
        c_p = assemble(got, "C", nb, b)
        c_j = assemble(ref, "C", nb, b)
        np.testing.assert_allclose(c_p, c_j, rtol=2e-5, atol=2e-5,
                                   err_msg=f"policy={policy}")
        np.testing.assert_allclose(c_p, a @ bm, rtol=2e-4, atol=2e-4)

    # Cholesky: trailing updates (syrk/gemm) through the pallas matmul
    progc = cholesky_program(nb, pr, pc, b)
    blkc, a_spd = make_spd_blocks(nb, b)
    packed_c = jnp.asarray(progc.pack(blkc))
    with mesh:
        got = progc.unpack(jax.jit(cholesky_executor(
            progc, mesh, matmul=task_matmul))(packed_c))
        ref = progc.unpack(jax.jit(cholesky_executor(progc, mesh))(packed_c))
    l_p = assemble_lower(got, nb, b)
    np.testing.assert_allclose(l_p, assemble_lower(ref, nb, b),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(l_p, np.linalg.cholesky(a_spd),
                               rtol=5e-3, atol=5e-3)

    # (b) attention chain: task (l) self-attends the previous layer's block
    depth, seq, dim = 6, 32, 16
    n_sh = 2
    mesh2 = _mesh(n_sh)

    def attn_graph():
        g = Graph("attnchain", n_shards=n_sh, owner=lambda blk: blk[1] % n_sh,
                  block_shape=(seq, dim))
        g.task_type("src",                    # publish the input as a task
                    space=lambda: ((0,),),    # output (communicated blocks
                    writes=lambda l: ("x", 0),  # are single-assignment)
                    reads=lambda l: [("in", 0)])
        g.task_type("attn",
                    space=lambda: ((l,) for l in range(1, depth + 1)),
                    writes=lambda l: ("x", l),
                    reads=lambda l: [("x", l - 1)] * 3)
        return g

    rng = np.random.default_rng(7)
    ablocks = {("in", 0): rng.standard_normal((seq, dim)).astype(np.float32)}
    for l in range(depth + 1):
        ablocks[("x", l)] = np.zeros((seq, dim), np.float32)

    aprog = attn_graph().to_program()
    apacked = jnp.asarray(aprog.pack(ablocks))
    jnp_body = {"src": lambda x: x,
                "attn": lambda q, k, v: mha_ref(
                    q[None, None], k[None, None], v[None, None],
                    causal=True)[0, 0]}
    pl_body = {"src": lambda x: x, "attn": task_attention}
    with mesh2:
        got = aprog.unpack(jax.jit(
            aprog.auto_executor(pl_body, mesh2))(apacked))
        ref = aprog.unpack(jax.jit(
            aprog.auto_executor(jnp_body, mesh2))(apacked))
    for l in range(1, depth + 1):
        np.testing.assert_allclose(got[("x", l)], ref[("x", l)],
                                   rtol=2e-5, atol=2e-5, err_msg=f"x{l}")


def case_pipeline_train_step():
    """Stage-parallel training on a ("pipe", "data", "model") mesh: the
    pipelined loss equals the sequential lm_loss, and two steps run with
    finite metrics (the launch.train --pipeline path)."""
    from repro.configs.base import reduced
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import lm_loss
    from repro.train.data import SyntheticLM
    from repro.train.train_step import (init_train_state,
                                        make_pipeline_train_step)

    _require_devices(4)
    cfg = reduced(get_config("starcoder2-3b"), n_layers=4, vocab_size=128)
    mesh = make_mesh((2, 2, 1), ("pipe", "data", "model"))
    params, opt = init_train_state(cfg, jax.random.key(0))
    ds = SyntheticLM(cfg.vocab_size, 32, 8, learnable=True)
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}

    step = jax.jit(make_pipeline_train_step(cfg, mesh, lr=1e-3, n_micro=4))
    p1, o1, m1 = step(params, opt, batch)
    ref = float(lm_loss(cfg, params, batch))
    got = float(m1["loss"])
    assert abs(got - ref) <= 1e-3 * max(1.0, abs(ref)), (got, ref)
    p1, o1, m2 = step(p1, o1, batch)
    assert np.isfinite(float(m2["loss"])) and float(m2["loss"]) < got

    # unsupported family fails loudly, not silently sequentially
    moe = reduced(get_config("deepseek-v3-671b"))
    try:
        make_pipeline_train_step(moe, mesh, n_micro=4)
    except ValueError as e:
        assert "dense family" in str(e)
    else:
        raise AssertionError("moe config should be rejected")


def case_pipeline_matches_sequential():
    from functools import reduce

    from repro.dist.pipeline import (pipeline_apply, pipeline_loss_fn,
                                     schedule_depth, split_microbatches)

    assert schedule_depth(4, 6) == 4 + 6 - 1  # PTG-derived GPipe bubble

    n_stages, n_micro, mb, d = 4, 8, 4, 16
    _require_devices(n_stages)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_stages]), ("pipe",))
    key = jax.random.key(0)
    params = jax.random.normal(key, (n_stages, d, d)) * (d ** -0.5)

    def stage_fn(p, x):
        return jnp.tanh(x @ p)

    xs = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
    with mesh:
        ys = pipeline_apply(stage_fn, params, xs, mesh=mesh)
    want = xs
    for s in range(n_stages):
        want = jnp.tanh(want @ params[s])
    np.testing.assert_allclose(np.asarray(ys), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    # gradients flow through the (reversed) pipeline — bwd by autodiff
    batch_x = xs.reshape(n_micro * mb, d)
    batch_y = jax.random.normal(jax.random.key(2), (n_micro * mb, d))
    loss = pipeline_loss_fn(stage_fn, lambda yh, y: jnp.mean((yh - y) ** 2),
                            mesh=mesh, n_micro=n_micro)

    def ref_loss(p, x, y):
        h = x
        for s in range(n_stages):
            h = jnp.tanh(h @ p[s])
        return jnp.mean((h - y) ** 2)

    with mesh:
        g_pipe = jax.grad(loss)(params, batch_x, batch_y)
    g_ref = jax.grad(ref_loss)(params, batch_x, batch_y)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def case_elastic_restore_smaller_mesh():
    """Checkpoint on a 2x4 mesh, restore re-sharded onto 1x4 (node loss)."""
    import tempfile

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.train import checkpoint as ckpt
    from repro.train.elastic import plan_remesh

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "b": jnp.ones((8,), jnp.float32)}
    _require_devices(8)
    mesh8 = jax.sharding.Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                              ("data", "model"))
    sh8 = {"w": NamedSharding(mesh8, P("data", "model")),
           "b": NamedSharding(mesh8, P("model"))}
    tree8 = jax.tree.map(jax.device_put, tree, sh8)

    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 7, tree8)
        assert ckpt.latest_step(d) == 7
        plan = plan_remesh(n_hosts=2, failed=[1], chips_per_host=4,
                           model_axis=4, latest_ckpt=7)
        assert plan.mesh_shape == (1, 4)
        mesh4 = jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
        sh4 = {"w": NamedSharding(mesh4, P("data", "model")),
               "b": NamedSharding(mesh4, P("model"))}
        restored = ckpt.restore(d, 7, tree, shardings=sh4)
        np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
        np.testing.assert_array_equal(np.asarray(restored["b"]), tree["b"])


def case_chip_smoke_four_chips():
    """chip_smoke.py's --four-chips phase at a tiny size on forced host
    devices: the 2x2-sharded Cholesky matches the one-shard run and every
    device holds its shard."""
    import chip_smoke

    _require_devices(4)
    jax.config.update("jax_default_matmul_precision", "highest")
    f = chip_smoke.four_chips_phase(jax.devices(), 4, 128, 0)
    assert f["devices_holding_shards"] == 4, f
    assert f["owned_blocks_written_on_owner"], f
    assert f["max_rel_diff"] <= f["bound"], f


def case_trace_scopes():
    """The 2x2 union-cover scan: every operation of the executor's work sits
    under a ``ttor.`` scope, the exchange's among them; and the counters its
    lowering sets equal a recount from the tables and ``comm_stats``."""
    from collections import Counter

    from repro.core import trace
    from repro.linalg.cholesky import cholesky_executor, cholesky_graph
    from tests.test_trace import scoped_hlo_case

    mesh = _mesh(4)
    prog = cholesky_graph(4, 2, 2, 128).to_program()
    for overlap in (True, False):
        plan = prog.plan_lowering(unroll_cap=2, overlap=overlap)
        assert plan["mode"] == "union_cover", plan
    found = scoped_hlo_case(4, 2, 2, 128, mesh, unroll_cap=2)
    assert "ttor.exchange" in found, found

    # without overlap, each union segment runs every wavefront's tables
    # padded to the segment's longest, on every shard
    trace.reset()
    cholesky_executor(prog, mesh, unroll_cap=2, overlap=False)
    got = trace.snapshot()["gauges"]
    rows = Counter()
    for s, e in prog.segments(comm="auto", cover="union"):
        for t in prog.types:
            longest = max((prog.tables[w][t][0].shape[1] for w in range(s, e)
                           if t in prog.tables[w]), default=0)
            rows[t] += prog.spec.n_shards * (e - s) * longest
    assert got["ttor.task_rows_by_type"] == dict(rows), got
    assert got["ttor.task_rows"] == sum(rows.values())
    stats = prog.comm_stats(comm="auto", segmented=True, cover="union")
    assert got["ttor.wire_slots"] == sum(
        w["wire_blocks"] for w in stats["per_wavefront"])
    assert got["ttor.wire_blocks"] == sum(p.total for p in prog.patterns) > 0
    assert got["ttor.wire_slots"] > got["ttor.wire_blocks"]


def case_indexed_bodies():
    """Four shards, every lowering: Cholesky whose syrk and gemm read their
    operands from the slot array by index (``task_matmul``'s indexed form,
    interpreted) matches the jnp bodies within float32 rounding, and the
    lowering counts exactly those rows as ``ttor.indexed_rows``."""
    from repro.core import trace
    from repro.kernels.block_gemm.ops import task_matmul
    from repro.linalg.cholesky import (assemble_lower, cholesky_bodies,
                                       cholesky_graph, make_spd_blocks)

    nb, pr, pc, b = 6, 2, 2, 8
    prog = cholesky_graph(nb, pr, pc, b).to_program()
    blocks, _ = make_spd_blocks(nb, b)
    packed = jnp.asarray(prog.pack(blocks))
    mesh = _mesh(pr * pc)
    variants = (
        dict(scan=False),
        dict(scan=False, overlap=True),
        dict(scan=True),
        dict(scan=True, comm="auto"),
        dict(scan=True, comm="auto", overlap=True),
        dict(scan=True, comm="auto", cover="union"),
        dict(scan=True, comm="auto", overlap=True, cover="union"),
    )
    with mesh:
        want = assemble_lower(prog.unpack(jax.jit(prog.executor(
            cholesky_bodies(), mesh, scan=False))(packed)), nb, b)
        for kw in variants:
            trace.reset()
            run = jax.jit(prog.executor(cholesky_bodies(task_matmul), mesh,
                                        **kw))
            rows = trace.snapshot()["gauges"]
            got = assemble_lower(prog.unpack(run(packed)), nb, b)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{kw}")
            by_type = rows["ttor.task_rows_by_type"]
            assert (rows["ttor.indexed_rows"]
                    == by_type["syrk"] + by_type["gemm"] > 0), (kw, rows)


ALL = {name[5:]: fn for name, fn in list(globals().items())
       if name.startswith("case_")}


def main(argv):
    names = argv or sorted(ALL)
    for name in names:
        try:
            ALL[name]()
        except SkipCase as e:
            print(f"CASE {name} SKIP ({e})", flush=True)
            continue
        print(f"CASE {name} OK", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
