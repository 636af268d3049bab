"""Production training launcher: mesh + shardings + elastic step loop.

    python -m repro.launch.train --arch qwen3-14b --steps 1000 \
        [--multi-pod] [--microbatch 4] [--ckpt-dir ...] [--host-devices N]

On hardware this runs under one controller per host (jax.distributed);
here `--host-devices N` forces N host devices so the full code path —
production mesh, sharded state, donated step, async checkpointing,
straggler monitor, elastic restart — executes identically at toy scale.
"""

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--pipeline", type=int, default=0, metavar="STAGES",
                    help="stage-parallel training on a ('pipe', 'data', "
                         "'model') mesh: the layer stack splits into STAGES "
                         "pipeline stages (repro.dist.pipeline; stage graph "
                         "from the repro.ptg builder). Microbatch count = "
                         "--microbatch if > 1 else 2*STAGES (GPipe rule).")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host devices (dev runs)")
    ap.add_argument("--data", default=None)
    ap.add_argument("--ckpt-dir", default="ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--elastic", action="store_true",
                    help="run the heartbeat/straggler/re-mesh decision loop "
                         "around the step loop: on a declared host failure "
                         "the survivors re-mesh (model axis fixed, data "
                         "axis shrunk) and restore the latest checkpoint")
    ap.add_argument("--fake-hosts", type=int, default=0,
                    help="with --elastic at dev scale: pretend the host "
                         "devices are split across N hosts")
    ap.add_argument("--kill-host", default=None, metavar="HOST@STEP",
                    help="dev fault injection: fake host HOST stops "
                         "heartbeating at STEP")
    ap.add_argument("--lease", type=float, default=2.0,
                    help="steps without a heartbeat before a host is "
                         "declared dead (--elastic)")
    ap.add_argument("--transport", default=None,
                    choices=("inproc", "multiproc"),
                    help="with --elastic: comm backend for the cross-host "
                         "control-plane preflight (every host exchanges "
                         "active messages over it before the step loop — "
                         "multiproc proves the path out of the process, "
                         "the jax.distributed-style bootstrap)")
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    import jax

    from repro.configs.base import reduced as reduce_cfg
    from repro.configs.registry import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.train.elastic import ElasticController

    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    seq = args.seq or (128 if args.reduced else 4096)
    global_batch = args.global_batch or (8 if args.reduced else 256)

    all_devices = list(jax.devices())
    controller = None
    kill_host = kill_at = None
    chips_per_host = len(all_devices)
    if args.elastic:
        if args.pipeline > 1:
            sys.exit("--elastic does not compose with --pipeline yet")
        fake_hosts = args.fake_hosts or 1
        if len(all_devices) % fake_hosts:
            sys.exit(f"--fake-hosts {fake_hosts} does not divide "
                     f"{len(all_devices)} devices")
        chips_per_host = len(all_devices) // fake_hosts
        controller = ElasticController(
            n_hosts=fake_hosts, chips_per_host=chips_per_host,
            model_axis=max(1, min(4, chips_per_host)),
            dead_after=args.lease)
        if args.kill_host:
            kh, ka = args.kill_host.split("@")
            kill_host, kill_at = int(kh), int(ka)
        if args.transport:
            _transport_preflight(args.transport, fake_hosts)

    devices = list(all_devices)
    shape_override = None  # set by a re-mesh plan after a host failure
    end = None  # absolute final step, fixed across re-meshes

    while True:
        plan, end = _run_epoch(args, cfg, seq, global_batch, devices,
                               shape_override, controller, kill_host,
                               kill_at, end)
        if plan is None:
            break
        devices = [all_devices[h * chips_per_host + c]
                   for h in plan.survivors for c in range(chips_per_host)]
        shape_override = plan.mesh_shape


def _preflight_main(ctx):
    got = []
    am = ctx.comm.make_active_msg(lambda src: got.append(src))
    for d in range(ctx.n_ranks):
        if d != ctx.rank:
            am.send(d, ctx.rank)
    ctx.barrier_free_join()
    return len(got)


def _transport_preflight(transport: str, n_hosts: int) -> None:
    """Cross-host control-plane bootstrap over the pluggable comm backend
    (``repro.core.comm``): every host sends an active message to every
    other and distributed completion drains the full set — the
    jax.distributed-style rendezvous, run over real OS processes under
    ``--transport multiproc``. Fails loudly before the step loop if any
    host pair cannot exchange messages."""
    from repro.core import run_ranks

    t0 = time.time()
    counts = run_ranks(n_hosts, _preflight_main, transport=transport)
    dt = time.time() - t0
    if counts != [n_hosts - 1] * n_hosts:
        sys.exit(f"transport preflight failed: per-host AM counts {counts}")
    print(f"transport preflight [{transport}]: {n_hosts} hosts all-to-all "
          f"({n_hosts * (n_hosts - 1)} AMs) in {dt * 1e3:.1f}ms", flush=True)


def _run_epoch(args, cfg, seq, global_batch, devices, shape_override,
               controller, kill_host, kill_at, end):
    """One mesh-lifetime of the step loop. Returns ``(plan, end)``:
    ``plan`` is None on normal completion, else the ElasticPlan that
    triggered a re-mesh (the caller rebuilds the survivor mesh and calls
    again; restore-from-checkpoint happens on the way back in)."""
    import sys
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist.ctx import set_batch_axes, set_seq_shard, use_mesh
    from repro.dist.sharding import (batch_axis, named_shardings,
                                     param_specs, sanitize_specs)
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.train import checkpoint as ckpt
    from repro.train.data import PackedBinaryDataset, SyntheticLM
    from repro.train.elastic import StragglerDetector
    from repro.train.optimizer import make_optimizer, opt_state_specs
    from repro.train.train_step import (init_train_state,
                                        make_pipeline_train_step,
                                        make_train_step)

    n_dev = len(devices)
    if shape_override is not None:
        mesh = jax.sharding.Mesh(
            np.array(devices).reshape(shape_override), ("data", "model"))
    elif args.pipeline > 1:
        # stage parallelism: ("pipe", "data", "model") — the ROADMAP's
        # pipeline_apply wiring; stage graph from the unified PTG builder
        from repro.models.transformer import layer_kinds

        if set(layer_kinds(cfg)) != {"dense"}:
            sys.exit(f"--pipeline supports the dense family for now; "
                     f"{cfg.name} is {cfg.family!r}")
        if n_dev % args.pipeline:
            sys.exit(f"--pipeline {args.pipeline} does not divide "
                     f"{n_dev} devices")
        mesh = make_mesh((args.pipeline, n_dev // args.pipeline, 1),
                         ("pipe", "data", "model"))
    elif n_dev >= 512 and args.multi_pod:
        mesh = make_production_mesh(multi_pod=True)
    elif n_dev >= 256:
        mesh = make_production_mesh()
    else:  # dev-scale mesh of the same shape family
        model = (controller.model_axis if controller is not None
                 else max(1, min(4, n_dev)))
        mesh = jax.sharding.Mesh(
            np.array(devices).reshape(n_dev // model, model),
            ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"arch={cfg.name} ({cfg.n_params() / 1e9:.2f}B params), "
          f"seq={seq} batch={global_batch}")

    set_batch_axes(batch_axis(mesh, global_batch))
    set_seq_shard(seq % mesh.shape["model"] == 0)

    with use_mesh(mesh):
        p_abs = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.key(0)))
        p_specs = sanitize_specs(
            param_specs(cfg, model_axis=mesh.shape["model"]), p_abs[0], mesh)
        if args.pipeline > 1:
            # per-stage parameter stacking: each stage holds its slice of
            # the layer stack (dim 0 of every "dense" leaf over "pipe")
            from jax.sharding import PartitionSpec as P

            if cfg.n_layers % args.pipeline:
                sys.exit(f"{cfg.n_layers} layers do not split into "
                         f"{args.pipeline} equal pipeline stages")
            p_specs["dense"] = jax.tree.map(lambda _: P("pipe"),
                                            p_abs[0]["dense"])
        o_specs = sanitize_specs(
            opt_state_specs(p_specs, cfg.optimizer, p_abs[0]), p_abs[1], mesh)
        p_sh = named_shardings(mesh, p_specs)
        o_sh = named_shardings(mesh, o_specs)

        # init sharded (jit'd init writes each shard on its device)
        params, opt_state = jax.jit(
            lambda k: init_train_state(cfg, k),
            out_shardings=(p_sh, o_sh))(jax.random.key(0))

        start = 0
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            print(f"elastic restore from step {latest} "
                  f"(mesh-shape independent)")
            state = ckpt.restore(args.ckpt_dir, latest,
                                 {"params": params, "opt": opt_state},
                                 shardings={"params": p_sh, "opt": o_sh})
            params, opt_state = state["params"], state["opt"]
            start = latest

        if args.data:
            ds = PackedBinaryDataset(args.data, seq, global_batch)
        else:
            ds = SyntheticLM(cfg.vocab_size, seq, global_batch,
                             embed_dim=cfg.d_model if cfg.embed_inputs
                             else None, encdec=cfg.family == "encdec",
                             learnable=args.reduced)

        if args.pipeline > 1:
            n_micro = (args.microbatch if args.microbatch > 1
                       else 2 * args.pipeline)
            if global_batch % n_micro:
                sys.exit(f"batch {global_batch} does not split into "
                         f"{n_micro} microbatches")
            step_fn = jax.jit(
                make_pipeline_train_step(cfg, mesh, lr=args.lr,
                                         n_micro=n_micro),
                donate_argnums=(0, 1))
        else:
            step_fn = jax.jit(
                make_train_step(cfg, lr=args.lr,
                                microbatches=args.microbatch),
                donate_argnums=(0, 1))
        saver = ckpt.AsyncCheckpointer(args.ckpt_dir, keep=3)
        monitor = StragglerDetector()
        if end is None:
            end = start + args.steps

        for step in range(start, end):
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(step).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            dt = time.time() - t0
            monitor.record(0, dt)  # per-host on a real cluster
            if step % 10 == 0 or step == end - 1:
                print(f"step {step:6d}  loss {float(metrics['loss']):8.4f}  "
                      f"|g| {float(metrics['grad_norm']):8.3f}  "
                      f"{global_batch * seq / dt:10.0f} tok/s", flush=True)
            if step and step % args.ckpt_every == 0:
                saver.save(step, {"params": params, "opt": opt_state})
            if controller is not None:
                # fake-host heartbeats: one controller step == one train
                # step (`now` is the step index, lease in steps). A real
                # cluster beats with wall time from every host.
                for h in controller.alive():
                    if not (h == kill_host and step >= kill_at):
                        controller.beat(h, dt, now=float(step))
                plan = controller.poll(ckpt.latest_step(args.ckpt_dir),
                                       now=float(step))
                if plan is not None:
                    print(f"host failure: survivors {plan.survivors}, "
                          f"re-mesh {plan.mesh_shape}, restore step "
                          f"{plan.restore_step}", flush=True)
                    saver.wait()  # quiesce before tearing the mesh down
                    return plan, end
        saver.save(end - 1, {"params": params, "opt": opt_state})
        saver.wait()  # quiesce (completion rule) before exit
        print("done")
    return None, end


if __name__ == "__main__":
    main()
