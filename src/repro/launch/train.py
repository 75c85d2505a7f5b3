"""Distributed training launcher.

Builds the production mesh, shards params/optimizer/batch with the rule-based
partitioner, and runs the jitted train step. On this CPU container use
--dry-run-devices to emulate the mesh (same code path as a real pod slice —
on TPU the mesh maps onto real devices and nothing else changes).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
      --steps 4 --reduced            # runnable on CPU (1 device)
  PYTHONPATH=src python -m repro.launch.train --arch kimi-k2-1t-a32b \
      --dry-run-devices 512 --multi-pod --steps 1 --compile-only
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config + 1-device mesh (CPU-runnable)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--dry-run-devices", type=int, default=0,
                    help="force N host platform devices (set FIRST)")
    args = ap.parse_args()

    if args.dry_run_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.dry_run_devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import INPUT_SHAPES, get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.data.pipeline import SyntheticLM
    from repro.launch import partition
    from repro.launch.mesh import make_production_mesh, mesh_info, n_chips
    from repro.models.model import build
    from repro.training.optimizer import AdamW, AdamWState
    from repro.training.train_loop import make_train_step

    enable_compile_cache()
    cfg = get_config(args.arch)
    shape = INPUT_SHAPES[args.shape]
    if args.reduced:
        cfg = reduced(cfg)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        B, S = 4, 32
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        B, S = shape.global_batch, shape.seq_len
    minfo = mesh_info(mesh)
    n_model = mesh.shape["model"]
    n_dp = n_chips(mesh) // n_model
    dp = minfo["dp"] if len(minfo["dp"]) > 1 else minfo["dp"][0]
    print(f"mesh={dict(mesh.shape)} arch={cfg.name} batch={B} seq={S}")

    bundle = build(cfg, mesh_info=minfo if n_chips(mesh) > 1 else None)
    opt = AdamW()
    step_fn = make_train_step(bundle, opt, microbatches=args.microbatches)

    params_abs = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0)))
    pspecs = partition.param_specs(cfg, params_abs, n_model=n_model)
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    ospecs = AdamWState(P(), pspecs, pspecs)
    batch_abs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    bspecs = partition.batch_specs(batch_abs, dp=dp, n_dp=n_dp)

    jstep = jax.jit(step_fn, in_shardings=(ns(pspecs), ns(ospecs),
                                           ns(bspecs)))
    if args.compile_only:
        opt_abs = jax.eval_shape(opt.init, params_abs)
        compiled = jstep.lower(params_abs, opt_abs, batch_abs).compile()
        print("compiled ok;", compiled.memory_analysis())
        return

    with jax.default_device(jax.devices()[0]):
        params = bundle.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    data = SyntheticLM(cfg.vocab, seed=0)
    it = data.batches(B, S)
    for i in range(args.steps):
        batch = {"tokens": jnp.asarray(next(it))}
        params, opt_state, m = jstep(params, opt_state, batch)
        print(f"step {i}: loss={float(m['loss']):.4f} "
              f"gnorm={float(m['grad_norm']):.2f}")


if __name__ == "__main__":
    main()
