"""Stage-parallel (pipeline) execution lowered from PTG discovery.

The pipeline is expressed through the unified ``repro.ptg`` builder as the
same kind of parametrized task graph every app declares: task (s, m) =
"stage s applied to microbatch m" writes activation block ("act", s, m)
and reads ("act", s-1, m) (the hand-off), with an ``after`` control edge
(s, m-1) (a stage is a serial resource) — the edge functions are derived,
not hand-written. ``discover`` levels this PTG into the familiar GPipe
trapezoid — wavefront(s, m) = s + m, depth = n_stages + n_micro - 1 — and
its ``comm_plan(w)`` is exactly the set of (s, s+1) stage hand-offs live at
step w, each a fused buffer per (src, dst) pair. The lockstep lowering here
turns every wavefront into compute + one collective permute over that
plan's pairs — with maximal runs of equal permutation folded into
``jax.lax.scan`` (the segmented-scan policy of `core.schedule`, via the
shared ``segment_runs``), so deep pipelines emit O(n_stages) HLO — and the
host PTG runtime, the block executor (`core.schedule`), and this pipeline
all derive communication from one planning layer.

Backward runs by autodiff: the transpose of a collective permute is the
reversed permute, so the gradient pipeline is the forward trapezoid
mirrored — no hand-written schedule needed.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.discovery import PTG, WavefrontSchedule, segment_runs
from repro.ptg import Graph, IndexSpace


def pipeline_graph(n_stages: int, n_micro: int) -> Graph:
    """The pipeline as a declarative ``repro.ptg`` graph: task (s, m) writes
    activation block ("act", s, m) and reads the previous stage's hand-off
    ("act", s-1, m); the serial-resource edge (s, m-1) is a pure control
    ``after`` edge. Hand-off data deps, stage sequencing, and the single
    seed (0, 0) all derive from those declarations. Task keys stay the
    legacy (stage, micro) tuples. The (stage, micro) space is partitionable
    by stage, so each stage's ``derive_local`` pass 1 enumerates its own
    microbatch row instead of scanning the whole trapezoid."""
    g = Graph("pipeline", n_shards=n_stages, owner=lambda blk: blk[1])
    g.task_type(
        "stage",
        space=IndexSpace(
            lambda: ((s, m) for s in range(n_stages)
                     for m in range(n_micro)),
            lambda shard: ((shard, m) for m in range(n_micro)),
            size=n_stages * n_micro),
        key=lambda s, m: (s, m),
        writes=lambda s, m: ("act", s, m),
        reads=lambda s, m: [("act", s - 1, m)] if s else [],
        after=lambda s, m: [(s, m - 1)] if m else [])
    return g


def pipeline_ptg(n_stages: int, n_micro: int) -> PTG:
    """The pipeline's parametrized task graph; task keys are (stage, micro)."""
    return pipeline_graph(n_stages, n_micro).to_ptg()


def pipeline_schedule(n_stages: int, n_micro: int) -> WavefrontSchedule:
    """Discover + level the pipeline PTG (one shard per stage), through the
    default lazy per-shard derivation — each stage derives only its own
    (s, m) tasks plus the neighbor hand-offs, never the full trapezoid.
    Validation is on: the builder guarantees mutual-inverse edges by
    construction, and ``check_consistency`` re-asserts it over every
    discovered task (cheap at stage-graph sizes)."""
    return pipeline_graph(n_stages, n_micro).to_schedule(validate=True)


def schedule_depth(n_stages: int, n_micro: int) -> int:
    """Pipeline depth in wavefronts — the PTG-derived GPipe bubble:
    n_stages + n_micro - 1."""
    return pipeline_schedule(n_stages, n_micro).n_wavefronts


def split_microbatches(batch: Any, n_micro: int) -> Any:
    """Reshape every leaf [B, ...] -> [n_micro, B // n_micro, ...]."""

    def split(x):
        b = x.shape[0]
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    return jax.tree.map(split, batch)


def _stage_perms(sched: WavefrontSchedule) -> List[List[Tuple[int, int]]]:
    """Per-wavefront collective-permute patterns from the schedule's
    classified comm plan (each (src, dst) pair carries one batched buffer).

    The pipeline PTG's hand-offs are the extreme sparse case: every
    wavefront's :class:`~repro.core.discovery.CommPattern` is one partial
    permutation of multiplicity 1 (density ~ 1/n), so the lowering is a
    single ``ppermute`` round — the same sparse path the block executor
    picks below its density threshold. Checked here so a pipeline PTG
    change that breaks the single-round shape fails loudly instead of
    silently dropping hand-offs."""
    perms = []
    for w in range(sched.n_wavefronts):
        pat = sched.comm_pattern(w)
        rounds = pat.rounds()
        if pat.max_pair > 1 or len(rounds) > 1:
            raise ValueError(
                f"wavefront {w}: stage hand-offs must form one multiplicity-1"
                f" permutation round, got {pat.pair_counts}")
        # overlap structure: stage 0 feeds from the host batch (the only
        # halo-independent work per wavefront); every later stage consumes
        # the previous wavefront's hand-off. The lockstep loop below relies
        # on exactly this split.
        for shard, (indep, _dep) in enumerate(sched.halo_split(w)):
            if shard > 0 and indep:
                raise ValueError(
                    f"wavefront {w}: stage {shard} has halo-independent "
                    f"tasks {indep}; pipeline stages must feed from the "
                    "previous stage's hand-off")
        perms.append(list(rounds[0]) if rounds else [])
    return perms


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, xs: jax.Array, *, mesh: Mesh,
                   axis: Optional[str] = None,
                   scan_runs: bool = True) -> jax.Array:
    """Run ``n_micro`` microbatches through a stage-parallel pipeline.

    ``stage_params``: pytree whose leaves stack per stage on dim 0 (length =
    mesh axis size); ``xs``: [n_micro, mb, ...] microbatched inputs;
    returns [n_micro, mb, ...] = stage_{S-1}(... stage_0(xs)), numerically
    identical to applying the stages sequentially. Differentiable.

    The lowering uses the block executor's segmentation policy: maximal
    runs of equal hand-off permutation (``segment_runs`` over the per-
    wavefront comm patterns) each become one ``jax.lax.scan``. The GPipe
    trapezoid has ~``2·n_stages`` distinct ramp wavefronts around one
    steady-state run of length ``n_micro - n_stages + 2``, so a *deep*
    pipeline (many microbatches) emits O(n_stages) HLO instead of
    O(n_stages + n_micro) — the stage-graph analogue of the segmented-scan
    executor. ``scan_runs=False`` forces the fully unrolled lowering.
    """
    axis = axis or mesh.axis_names[0]
    n_stages = mesh.shape[axis]
    n_micro = xs.shape[0]
    sched = pipeline_schedule(n_stages, n_micro)
    perms = _stage_perms(sched)

    def run(p_local, xs_full):
        idx = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], p_local)
        # the carries differ per stage: mark them varying over the stage
        # axis so scan's carry types match what the stage bodies return
        recv, outs = jax.lax.pcast(
            (jnp.zeros(xs_full.shape[1:], xs_full.dtype),
             jnp.zeros_like(xs_full)), (axis,), to="varying")

        def wavefront(w, recv, outs, perm):
            m = w - idx                       # microbatch at this stage now
            m_c = jnp.clip(m, 0, n_micro - 1)
            x_in = jnp.where(idx == 0, xs_full[m_c], recv)
            y = stage_fn(p, x_in).astype(xs_full.dtype)
            active = (m >= 0) & (m < n_micro)
            done = active & (idx == n_stages - 1)
            outs = outs.at[m_c].set(jnp.where(done, y, outs[m_c]))
            if perm:                          # the wavefront's fused hand-off
                recv = jax.lax.ppermute(y, axis, perm)
            return recv, outs

        for start, stop in segment_runs([tuple(p_) for p_ in perms]):
            perm = list(perms[start])         # constant within the run
            if not scan_runs or stop - start == 1:
                for w in range(start, stop):
                    recv, outs = wavefront(w, recv, outs, perm)
            else:
                def step(carry, w, _perm=tuple(perms[start])):
                    r, o = wavefront(w, carry[0], carry[1], list(_perm))
                    return (r, o), None

                (recv, outs), _ = jax.lax.scan(
                    step, (recv, outs), jnp.arange(start, stop))
        # only the last stage holds real outputs; broadcast to all shards
        outs = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return jax.shard_map(run, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P())(stage_params, xs)


def pipeline_loss_fn(stage_fn: Callable[[Any, jax.Array], jax.Array],
                     loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
                     *, mesh: Mesh, n_micro: int,
                     axis: Optional[str] = None):
    """``loss(stage_params, batch_x, batch_y)`` through the pipeline —
    microbatches the batch, pipelines the forward, applies ``loss_fn`` on
    the re-assembled outputs; grads flow back through the reversed
    pipeline by autodiff."""

    def loss(stage_params, batch_x, batch_y):
        xs = split_microbatches(batch_x, n_micro)
        ys = pipeline_apply(stage_fn, stage_params, xs, mesh=mesh, axis=axis)
        yh = ys.reshape(batch_x.shape[0], *ys.shape[2:])
        return loss_fn(yh, batch_y)

    return loss
