"""Multi-device training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 50 --batch 8 --seq 128 [--devices 8] [--ckpt DIR]

On a real TPU pod slice this runs under the production mesh; on CPU pass
--devices N to force host devices (set before jax init).
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU testing; applies "
                         "with JAX_PLATFORMS=cpu only)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    args = ap.parse_args()

    if args.devices:
        from repro.launch.jax_setup import force_cpu_devices
        force_cpu_devices(args.devices)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_arch, smoke_config
    from repro.data.lm_data import synthetic_lm_batches
    from repro.distributed import sharding as shd
    from repro.distributed.act_sharding import use_dp_axes
    from repro.launch.mesh import make_smoke_mesh, dp_axes
    from repro.models import transformer as tr
    from repro.training import optimizer as opt
    from repro.training.train_loop import TrainConfig, lr_schedule

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    mesh = make_smoke_mesh()
    dp = dp_axes(mesh)
    print(f"mesh {dict(mesh.shape)} | arch {cfg.name}")

    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           shd.lm_param_specs(cfg),
                           is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, p_shard)
    opt_state = opt.init(params, opt.AdamWConfig())

    step0 = opt.make_train_step(
        lambda p, b: tr.train_loss(cfg, p, b,
                                   vocab_chunk_seq=min(args.seq, 512)),
        opt.AdamWConfig())

    def step(p, o, b):
        with use_dp_axes(dp):
            return step0(p, o, b)

    jstep = jax.jit(step, donate_argnums=(0, 1))
    data = synthetic_lm_batches(cfg.vocab_size, args.batch, args.seq)
    bshard = NamedSharding(mesh, P(dp, None))

    from repro.distributed import checkpoint as ck
    with mesh:
        for i in range(args.steps):
            b = next(data)
            b = {k: jax.device_put(jnp.asarray(v), bshard)
                 for k, v in b.items()}
            params, opt_state, m = jstep(params, opt_state, b)
            if (i + 1) % 5 == 0 or i == 0:
                print(f"step {i+1:4d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.2f}")
            if args.ckpt and (i + 1) % 20 == 0:
                ck.save(args.ckpt, i + 1,
                        {"params": params, "opt": opt_state})
    print("done")


if __name__ == "__main__":
    main()
