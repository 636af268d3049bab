"""Cross-backend transport conformance: every registered comm backend
must present the same contract to the runtime.

The backend registry (``repro.core.comm``) is only worth having if the
backends are interchangeable — same delivery semantics (per-pair FIFO,
exactly-once under loss and duplication), same failure surfacing
(``RankKilled`` -> ``None`` result + DEATH in the report, rank errors ->
``RuntimeError`` with the remote traceback), same channel lifecycle
(clean listener shutdown refuses new connects loudly). This suite runs
one body of assertions against every backend, in three flavors per the
world-level legs: plain, with a seeded loss+dup FaultPlan, and (for
``multiproc``) across real OS process boundaries.

The bit-identity tests are the PR's acceptance: the Task-Bench
dependence-pattern sweep and the blocked Cholesky must produce exactly
the same blocks whether the ranks are threads (``inproc``) or forked
processes wired over loopback TCP (``multiproc``) — same bodies on both
sides, so any divergence is a transport bug, not float noise.
"""

import os
import threading
import time

import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core import FaultPlan, run_ranks
from repro.core.comm import (CommClosedError, Wire, backend_names,
                             get_backend)
from repro.ptg import Graph, IndexSpace
from repro.linalg.cholesky import (assemble_lower, cholesky_bodies_numpy,
                                   cholesky_graph, make_spd_blocks)
from benchmarks.taskbench_scaling import (taskbench_blocks, taskbench_bodies,
                                          taskbench_graph)

BACKENDS = sorted(backend_names())
PATTERNS = ("stencil", "fft", "tree", "random")

# world-level legs: every backend plain AND under a seeded loss+dup plan.
# A plan is always passed explicitly (zero rates on the plain legs) so the
# REPRO_CHAOS conftest wrapper never stacks a second plan on top and the
# return shape is uniformly (results, report).
LEGS = [pytest.param(b, p, id=b if not p else f"{b}-lossdup")
        for b in BACKENDS for p in (0.0, 0.15)]


def _plan(p: float, seed: int = 5, **kw) -> FaultPlan:
    return FaultPlan(seed=seed, drop=p, duplicate=p, **kw)


# ------------------------------------------------------------- the registry

def test_registry_lists_both_backends():
    assert {"inproc", "multiproc"} <= set(backend_names())


def test_registry_unknown_backend_fails_loudly():
    with pytest.raises(KeyError, match="carrier-pigeon"):
        get_backend("carrier-pigeon")
    # the error names what IS registered, so the fix is in the message
    with pytest.raises(KeyError, match="inproc"):
        get_backend("carrier-pigeon")


def test_registry_env_var_is_the_default(monkeypatch):
    monkeypatch.delenv("REPRO_TRANSPORT", raising=False)
    assert get_backend(None).name == "inproc"
    monkeypatch.setenv("REPRO_TRANSPORT", "multiproc")
    assert get_backend(None).name == "multiproc"
    # an explicit argument always beats the environment
    assert get_backend("inproc").name == "inproc"


# ------------------------------------------- channel-level contract (Comm)

@pytest.mark.parametrize("backend_name", BACKENDS)
def test_channel_echo_roundtrip_and_clean_listener_shutdown(backend_name):
    """listener/connector/Comm alone, no world on top: payloads round-trip
    unchanged (including Wire dataclasses carrying ndarrays), and a
    stopped listener refuses new connects with CommClosedError instead of
    hanging."""
    backend = get_backend(backend_name)
    served = threading.Event()

    def echo(ch):
        served.set()
        try:
            while True:
                ch.write(ch.read(timeout=5.0))
        except (CommClosedError, TimeoutError):
            ch.close()

    lis = backend.listener(echo)
    lis.start()
    try:
        ch = backend.connector().connect(lis.address)
        for i in range(5):
            ch.write(("ping", i))
            assert ch.read(timeout=5.0) == ("ping", i)
        wire = Wire(kind="am", src=3, am_id=1, blob=b"\x00payload",
                    raw=np.arange(6, dtype=np.float32), seq=9)
        ch.write(wire)
        back = ch.read(timeout=5.0)
        assert (back.kind, back.src, back.am_id, back.blob, back.seq) == \
            ("am", 3, 1, b"\x00payload", 9)
        assert np.array_equal(back.raw, wire.raw)
        ch.close()
        assert ch.closed
    finally:
        lis.stop()
    # a stopped listener services nothing: connect either refuses loudly
    # (inproc; TCP usually too) or — loopback TCP can self-connect to a
    # dead ephemeral port — yields a channel no handler will ever serve
    served.clear()
    try:
        orphan = backend.connector().connect(lis.address, timeout=0.5)
    except CommClosedError:
        return
    time.sleep(0.2)
    assert not served.is_set()
    orphan.close()


# ------------------------------------------- world-level delivery semantics

@pytest.mark.parametrize("transport,p", LEGS)
def test_per_pair_fifo_exactly_once(transport, p):
    """Every rank streams sequence numbers to every other rank; each
    receiver must observe each source's stream complete and duplicate-
    free, and — on a fault-free transport — IN ORDER, the per-(src,dst)
    FIFO the §II-B2 AM model assumes. Under seeded loss the guarantee
    deliberately weakens to exactly-once: a dropped message is
    retransmitted after its successors were already processed (dedup is
    a cumulative seen-window, not a hold-back queue), which is exactly
    the reordering the completion counters must tolerate."""
    n, m = 3, 15

    def main(ctx):
        got = {}
        am = ctx.comm.make_active_msg(
            lambda src, i: got.setdefault(src, []).append(i))
        for dst in range(ctx.n_ranks):
            if dst != ctx.rank:
                for i in range(m):
                    am.send(dst, ctx.rank, i)
        ctx.tp.join()
        return got

    res, report = run_ranks(n, main, faults=_plan(p), timeout=90.0,
                            transport=transport)
    for r, got in enumerate(res):
        assert sorted(got) == [s for s in range(n) if s != r]
        for src, seqs in got.items():
            if p:
                assert sorted(seqs) == list(range(m)), \
                    f"rank {r} lost/doubled src {src}'s stream: {seqs}"
            else:
                assert seqs == list(range(m)), \
                    f"rank {r} saw src {src} out of order: {seqs}"
    if p:
        assert report.injected_drops + report.injected_dups > 0


@pytest.mark.parametrize("backend_name", BACKENDS)
def test_duplicates_suppressed_exactly_once(backend_name):
    plan = FaultPlan(seed=3, drop=0.0, duplicate=0.5)

    def main(ctx):
        received = []
        am = ctx.comm.make_active_msg(lambda i: received.append(i))
        if ctx.rank == 0:
            for i in range(30):
                am.send(1, i)
        ctx.tp.join()
        return received

    res, report = run_ranks(2, main, faults=plan, timeout=90.0,
                            transport=backend_name)
    assert res[1] == list(range(30))
    assert report.injected_dups > 0
    assert report.dup_suppressed > 0


@pytest.mark.parametrize("backend_name", BACKENDS)
def test_drops_recovered_by_retransmit(backend_name):
    plan = FaultPlan(seed=7, drop=0.3, duplicate=0.0)

    def main(ctx):
        received = []
        am = ctx.comm.make_active_msg(lambda i: received.append(i))
        if ctx.rank == 0:
            for i in range(30):
                am.send(1, i)
        ctx.tp.join()
        return received

    res, report = run_ranks(2, main, faults=plan, timeout=90.0,
                            transport=backend_name)
    # retransmits reorder but never lose or double (exactly-once)
    assert sorted(res[1]) == list(range(30))
    assert report.injected_drops > 0
    assert report.retries > 0


# --------------------------------------------------------- failure surfacing

@pytest.mark.parametrize("backend_name", BACKENDS)
def test_rank_kill_surfaces_death_and_survivors_drain(backend_name):
    """kill={1: 3}: the killed rank's result slot is None, the report
    carries the DEATH declaration, and the survivors' own streams are
    still delivered exactly once (no poisoning, no hang)."""
    plan = FaultPlan(seed=11, drop=0.05, duplicate=0.05, kill={1: 3})

    def main(ctx):
        received = []
        am = ctx.comm.make_active_msg(lambda i: received.append(i))
        if ctx.rank != 0:
            for i in range(10):
                am.send(0, ctx.rank * 100 + i)
        ctx.tp.join()
        return received

    res, report = run_ranks(3, main, faults=plan, timeout=90.0,
                            transport=backend_name)
    assert res[1] is None
    assert report.deaths == [1]
    got = sorted(res[0])
    # rank 2 survives: delivered exactly once; rank 1 died at its 3rd
    # send, so at most its first two arrive — never duplicated
    assert [x for x in got if x >= 200] == [200 + i for i in range(10)]
    from_dead = [x for x in got if x < 200]
    assert set(from_dead) <= {100, 101}
    assert len(from_dead) == len(set(from_dead))


@pytest.mark.parametrize("backend_name", BACKENDS)
def test_rank_error_propagates_with_remote_traceback(backend_name):
    def main(ctx):
        if ctx.rank == 1:
            raise ValueError("boom-evidence-42")
        ctx.tp.join()

    with pytest.raises(RuntimeError, match="rank 1 failed") as ei:
        run_ranks(2, main, faults=_plan(0.0), timeout=60.0,
                  transport=backend_name)
    # the failing rank's own traceback crosses the process boundary
    assert "boom-evidence-42" in str(ei.value)
    assert "ValueError" in str(ei.value)


def test_multiproc_ranks_are_real_processes():
    """The backend's whole point: ranks are OS processes, not threads."""
    def main(ctx):
        ctx.tp.join()
        return os.getpid()

    pids, _ = run_ranks(3, main, faults=_plan(0.0), timeout=60.0,
                        transport="multiproc")
    assert len(set(pids)) == 3
    assert os.getpid() not in pids


def test_multiproc_refuses_to_fork_from_a_process_holding_an_accelerator(
        monkeypatch):
    """A parent that has initialised JAX on an accelerator holds the device,
    so forked ranks running jnp bodies would fail or hang on it: run_ranks
    must refuse before it forks anything. The test stands in for an
    initialised TPU backend by patching JAX's backend registry view."""
    import multiprocessing

    import jax  # noqa: F401 — the check looks for an imported jax
    from jax._src import xla_bridge

    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(xla_bridge, "backends",
                        lambda: {"cpu": object(), "tpu": object()})
    forked = []
    monkeypatch.setattr(multiprocessing.context.ForkProcess, "start",
                        lambda self: forked.append(self))

    def main(ctx):
        ctx.tp.join()

    with pytest.raises(RuntimeError, match="initialised JAX on 'tpu'"):
        run_ranks(2, main, faults=_plan(0.0), timeout=10.0,
                  transport="multiproc")
    assert forked == []


# ------------------------------------------------- cross-backend bit-identity

@pytest.mark.parametrize("pattern", PATTERNS)
def test_taskbench_sweep_bit_identical_across_backends(pattern):
    blocks = taskbench_blocks(4, 3, seed=7)
    outs = {}
    for t in BACKENDS:
        g, _ = taskbench_graph(pattern, 4, 3, 2, seed=7)
        outs[t] = g.run_host(blocks, taskbench_bodies(), n_threads=2,
                             transport=t)
    ref = outs["inproc"]
    for t in BACKENDS:
        assert outs[t].keys() == ref.keys()
        for blk in ref:
            assert np.array_equal(np.asarray(outs[t][blk]),
                                  np.asarray(ref[blk])), (t, pattern, blk)


def test_cholesky_bit_identical_across_backends():
    """Same numpy bodies on both sides (the jax bodies are fork-hostile:
    a forked child must not call into the parent's XLA runtime), so the
    factor blocks must match bit for bit — and actually factorize A."""
    nb, b = 4, 4
    blocks, a = make_spd_blocks(nb, b, seed=7)
    outs = {t: cholesky_graph(nb, 2, 1, b).run_host(
                blocks, cholesky_bodies_numpy(), n_threads=2, transport=t)
            for t in BACKENDS}
    ref = outs["inproc"]
    for t in BACKENDS:
        assert outs[t].keys() == ref.keys()
        for blk in ref:
            assert np.array_equal(np.asarray(outs[t][blk]),
                                  np.asarray(ref[blk])), (t, blk)
    low = assemble_lower(ref, nb, b)
    np.testing.assert_allclose(low @ low.T, a, atol=1e-3)


# ------------------------------------- the resident scheduler, cross-process

def _mixed_stream_acceptance(n_clients: int, n_subs: int) -> None:
    """N clients x M mixed submissions (Task-Bench patterns + Cholesky)
    into a resident multiproc service; every result must be bit-identical
    to its one-shot inproc oracle (same bodies both sides)."""
    from repro.launch.scheduler import run_stream
    from repro.sched import SchedulerService

    width, depth, nb = 4, 3, 4
    with SchedulerService(2, n_threads=2, timeout=240.0,
                          transport="multiproc") as svc:
        results = run_stream(svc, n_clients, n_subs, width=width,
                             depth=depth, nb=nb)

    tb_blocks = taskbench_blocks(width, depth, seed=7)
    ch_blocks, _ = make_spd_blocks(nb, 4, seed=7)
    refs = {}
    for kind in {k for rows in results.values() for k, _ in rows}:
        if kind == "cholesky":
            refs[kind] = cholesky_graph(nb, 2, 1, 4).run_host(
                ch_blocks, cholesky_bodies_numpy(), n_threads=2)
        else:
            g, _ = taskbench_graph(kind, width, depth, 2, seed=7)
            refs[kind] = g.run_host(tb_blocks, taskbench_bodies(),
                                    n_threads=2)
    assert sorted(results) == [f"client{i}" for i in range(n_clients)]
    for name, rows in results.items():
        assert len(rows) == n_subs
        for kind, out in rows:
            assert out is not None
            for blk, v in out.items():
                assert np.array_equal(np.asarray(v),
                                      np.asarray(refs[kind][blk])), \
                    (name, kind, blk)


def test_multiproc_scheduler_mixed_stream_small():
    _mixed_stream_acceptance(2, 4)


@pytest.mark.skipif(not os.environ.get("REPRO_TRANSPORT_SOAK"),
                    reason="full 4x8 acceptance runs on the CI "
                           "transport-soak leg (REPRO_TRANSPORT_SOAK=1)")
def test_multiproc_scheduler_acceptance_4x8():
    """The ISSUE's acceptance scenario verbatim, cross-process: 4 clients
    x 8 mixed submissions on resident multiproc ranks."""
    _mixed_stream_acceptance(4, 8)


def _single_task_graph(name: str) -> Graph:
    g = Graph(name, n_shards=1, owner=lambda blk: 0)
    g.task_type("t", writes=lambda i: ("g", i), reads=lambda i: [("g", i)],
                space=IndexSpace(lambda: range(1), lambda s: [0], size=1))
    return g


def test_future_timeout_snapshot_crosses_the_process_boundary():
    """Satellite: SubmissionFuture.result's forensic snapshot used to
    read the rank runtimes through shared memory — impossible when the
    ranks are processes. It now rides a SNAPSHOT control message, so a
    timed-out future still names the stuck side cross-process."""
    bodies = {"t": lambda x: (time.sleep(1.2), x + 1.0)[1]}
    blocks = {("g", 0): np.float64(0)}
    from repro.sched import SchedulerService

    with SchedulerService(1, timeout=60.0, transport="multiproc") as svc:
        c = svc.client("alice")
        f = c.submit(_single_task_graph("slow"), blocks, bodies)
        with pytest.raises(TimeoutError) as ei:
            f.result(0.3)
        msg = str(ei.value)
        assert "scheduler snapshot" in msg
        assert "bus:" in msg and "unresolved" in msg
        assert "rank 0:" in msg       # fetched from the child process
        out = f.result(30.0)          # and the submission still completes
    assert float(out[("g", 0)]) == 1.0


# -------------------------- kill-point sweep, cross-process (hypothesis)

@settings(deadline=None, max_examples=3,
          suppress_health_check=[HealthCheck.too_slow])
@given(at=st.integers(1, 40))
def test_multiproc_kill_point_sweep_stream_bit_identical(at):
    """Property (extends the PR-9 sweep across the process boundary):
    kill resident rank 1 at ANY user-AM send index during a chained
    3-submission stream over ``multiproc`` — whatever the cut point, the
    stream drains bit-identical to the sequential one-shot oracle."""
    from repro.sched import SchedulerService

    m, W, D, S = 3, 4, 3, 2
    bodies = taskbench_bodies()
    blocks = taskbench_blocks(W, D, seed=at)
    refs, store = [], dict(blocks)
    for _ in range(m):
        g, _ = taskbench_graph("stencil", W, D, S, seed=at)
        out = g.run_host(store, bodies, n_threads=2)
        refs.append(out)
        store.update(out)

    plan = FaultPlan(seed=at, kill={1: at}, lease=0.4, heartbeat_every=0.02)
    with SchedulerService(S, timeout=90.0, faults=plan,
                          transport="multiproc") as svc:
        c = svc.client("alice")
        futs = []
        for j in range(m):
            g, _ = taskbench_graph("stencil", W, D, S, seed=at)
            futs.append(c.submit(g, blocks if j == 0 else {}, bodies))
        outs = [f.result(90.0) for f in futs]
    for out, ref in zip(outs, refs):
        assert set(out) == set(ref)
        for blk in ref:
            assert np.array_equal(np.asarray(out[blk]),
                                  np.asarray(ref[blk])), (at, blk)
