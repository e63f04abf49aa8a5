"""Distributed training launcher.

Runs the pjit train step under a mesh with FSDP/TP/EP sharding, fault
tolerance (auto-resume from the latest checkpoint, SIGTERM-safe save),
and the straggler watchdog. On this CPU container it runs reduced configs
on the host mesh; on a real cluster the same entry point runs the full
configs on the production mesh (launch with --production-mesh under
jax.distributed initialization — one process per host).

Usage:
  python -m repro.launch.train --arch smollm-135m --smoke --steps 50
  python -m repro.launch.train --arch qwen3-8b --smoke --steps 100 \
      --ckpt-dir /tmp/ckpt --global-batch 8
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, get_smoke_config
from repro.core import api
from repro.core.plan import GemmPolicy
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.distributed import sharding as shd
from repro.launch import steps as ST
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as T
from repro.obs import Timer
from repro.optim import AdamWConfig, adamw_init
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import TrainConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (requires 256 devices)")
    ap.add_argument("--gemm-backend", default="auto",
                    help="GEMM backend (auto|xla|pallas|"
                         "blockflow|<registered>)")
    ap.add_argument("--gemm-mode", default="auto",
                    choices=["auto", "dc", "dm"],
                    help="paper access mode; auto = per-shape sysmodel pick")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    rules = ST.make_rules(cfg, mesh)
    policy = GemmPolicy(backend=args.gemm_backend, mode=args.gemm_mode)
    print(f"[train] arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"steps={args.steps} gemm={policy.resolved_backend()}/{policy.mode}")

    tc = TrainConfig(steps=args.steps, log_every=args.log_every,
                     ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                     seed=args.seed, base_lr=args.lr, warmup=args.warmup,
                     compress_grads=args.compress_grads, gemm=policy)
    opt_cfg = AdamWConfig(lr=args.lr, compress_grads=args.compress_grads)
    dc = DataConfig(seq_len=args.seq_len, global_batch=args.global_batch,
                    vocab=cfg.vocab, seed=args.seed,
                    n_codebooks=cfg.n_codebooks)
    data = TokenPipeline(dc)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    with shd.use_rules(rules), api.use_policy(policy):
        params, axes = T.init_model(jax.random.PRNGKey(args.seed), cfg)
        opt_state = adamw_init(params)
        p_shard = ST.model_shardings(cfg, params, axes, rules)
        o_shard = ST.opt_shardings(p_shard, rules)
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, o_shard)

        start = 0
        if ckpt and ckpt.latest_step() is not None:
            state = ckpt.restore({"params": params, "opt": opt_state},
                                 shardings={"params": p_shard,
                                            "opt": o_shard})
            params, opt_state = state["params"], state["opt"]
            meta = ckpt.meta()
            start = meta["step"]
            data.load_state_dict(meta["extra"]["data"])
            print(f"[train] resumed from step {start}")

        step_fn = ST.make_train_step_fn(
            cfg, opt_cfg, total_steps=args.steps)
        sample = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        data.load_state_dict({"cursor": data.cursor - 1, "seed": args.seed})
        b_shard = ST.batch_shardings(sample, rules)
        jitted = jax.jit(step_fn, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))

        ema = None
        for step in range(start, args.steps):
            with Timer() as tm:
                batch = {k: jnp.asarray(v)
                         for k, v in data.next_batch().items()}
                params, opt_state, metrics = jitted(params, opt_state,
                                                    batch)
            dt = tm.dt
            ema = dt if ema is None else 0.9 * ema + 0.1 * dt
            if dt > 3.0 * ema:
                print(f"[watchdog] step {step} straggled "
                      f"({dt:.2f}s vs EMA {ema:.2f}s)")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step} loss {float(metrics['loss']):.4f}"
                      f" grad_norm {float(metrics['grad_norm']):.3f}"
                      f" ({dt * 1e3:.0f} ms)")
            if ckpt and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state},
                          extra={"data": data.state_dict()})
        if ckpt:
            ckpt.save(args.steps, {"params": params, "opt": opt_state},
                      extra={"data": data.state_dict()})
            ckpt.wait()
    print("[train] done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
