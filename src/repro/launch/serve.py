"""Production serving launcher: sharded weights + batched decode loop.

    python -m repro.launch.serve --arch yi-6b --reduced --host-devices 4 \
        --batch 8 --tokens 64
"""

import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0)
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.configs.base import reduced as reduce_cfg
    from repro.configs.registry import get_config
    from repro.dist.ctx import set_batch_axes, set_seq_shard, use_mesh
    from repro.dist.sharding import (batch_axis, cache_specs, kv_head_pad,
                                     named_shardings, param_specs,
                                     sanitize_specs)
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.models import transformer as tfm
    from repro.serve.decode import make_serve_step

    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)

    n_dev = len(jax.devices())
    if n_dev >= 256:
        mesh = make_production_mesh()
    else:
        model = max(1, min(4, n_dev))
        mesh = make_mesh((n_dev // model, model), ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"arch={cfg.name}")

    set_batch_axes(batch_axis(mesh, args.batch))
    set_seq_shard(False)

    with use_mesh(mesh):
        params_abs = tfm.abstract_params(cfg)
        p_specs = sanitize_specs(
            param_specs(cfg, model_axis=mesh.shape["model"]), params_abs,
            mesh)
        p_sh = named_shardings(mesh, p_specs)
        params = jax.jit(lambda k: tfm.init_params(cfg, k),
                         out_shardings=p_sh)(jax.random.key(0))

        enc_out = None
        if cfg.family == "encdec":
            hd, hkv = cfg.head_dim, cfg.n_kv_heads
            enc_out = tuple(
                jnp.zeros((cfg.n_layers, args.batch, hkv, args.max_seq, hd),
                          jnp.bfloat16) for _ in range(2))
        cache = tfm.init_cache(cfg, args.batch, args.max_seq, enc_out=enc_out,
                               kv_head_pad=kv_head_pad(
                                   cfg, mesh.shape["model"]))
        c_specs = sanitize_specs(
            cache_specs(cfg, jax.eval_shape(lambda: cache),
                        batch_axis(mesh, args.batch),
                        model_axis=mesh.shape["model"]),
            jax.eval_shape(lambda: cache), mesh)
        cache = jax.tree.map(
            lambda x, s: jax.device_put(x, jax.NamedSharding(mesh, s)),
            cache, c_specs, is_leaf=lambda x: hasattr(x, "shape"))

        step = jax.jit(lambda p, t, c: make_serve_step(cfg)(p, t, c),
                       donate_argnums=(2,))
        tok = jnp.ones((args.batch,), jnp.int32)
        tok, _, cache = step(params, tok, cache)  # warmup/compile
        t0 = time.time()
        out = []
        for _ in range(args.tokens):
            tok, _, cache = step(params, tok, cache)
            out.append(np.asarray(tok))
        dt = time.time() - t0
        print(f"decoded {args.tokens} x batch {args.batch}: "
              f"{args.batch * args.tokens / dt:.1f} tok/s; "
              f"sample {np.stack(out, 1)[0][:12].tolist()}")


if __name__ == "__main__":
    main()
