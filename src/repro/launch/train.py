"""Training driver.

CPU-smoke example:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-110b --smoke \
      --steps 50 --global-batch 8 --seq-len 128 --quant qat4

On a real slice the same driver runs under the production mesh
(``--mesh single|multi``); the loop is identical: sharded state, jitted
train_step, async checkpoints every ``--ckpt-every``, heartbeat every step,
restart-from-latest on relaunch.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint.checkpointer import Checkpointer
from ..core.packed_linear import LinearSpec
from ..data.pipeline import DataConfig, SyntheticStream
from ..models import transformer as T
from ..models.registry import get_config
from ..optim.adamw import AdamWConfig, adamw_init
from ..optim.schedule import cosine_with_warmup
from ..runtime.compile_cache import enable_compile_cache
from ..runtime.fault_tolerance import Heartbeat
from ..runtime.sharding import param_shardings
from .mesh import make_local_mesh, make_production_mesh
from .steps import make_train_step


def build_state(cfg, mesh, opt_cfg, seed=0):
    params_shape = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    )
    p_shard = param_shardings(params_shape, mesh)
    init = jax.jit(
        lambda k: T.init_params(k, cfg, jnp.float32), out_shardings=p_shard
    )
    params = init(jax.random.PRNGKey(seed))
    opt = jax.jit(adamw_init, out_shardings={"m": p_shard, "v": p_shard, "step": NamedSharding(mesh, P())})(params)
    return {"params": params, "opt": opt}, p_shard


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--quant", default="native",
                    choices=["native", "qat4", "qat8", "int8"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="local", choices=["local", "single", "multi"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    cfg = dataclasses.replace(cfg, quant=LinearSpec(mode=args.quant))
    if args.mesh == "local":
        mesh = make_local_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    opt_cfg = AdamWConfig(lr=args.lr)
    sched = cosine_with_warmup(args.lr, warmup=max(args.steps // 10, 1), total=args.steps)
    state, p_shard = build_state(cfg, mesh, opt_cfg)
    if args.compress_grads:
        from ..runtime.compression import init_error_feedback

        state["error_buf"] = init_error_feedback(state["params"])

    data = SyntheticStream(
        DataConfig(cfg.vocab_size, args.seq_len + 1, args.global_batch)
    ).start()
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    hb = Heartbeat(args.ckpt_dir + "/hb", 0) if args.ckpt_dir else None
    start_step = 0
    if ckpt and ckpt.latest_step() is not None:
        state, extra = ckpt.restore(ckpt.latest_step(), state)
        data.load_state_dict(extra["data"])
        start_step = extra["train_step"]
        print(f"[train] resumed from step {start_step}")

    step_fn = jax.jit(
        make_train_step(
            cfg, opt_cfg, mesh, sched, compress_grads=args.compress_grads
        ),
        donate_argnums=(0,),
    )

    with mesh:
        t0 = time.time()
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            state, metrics = step_fn(state, batch)
            if hb:
                hb.beat(step)
            if step % args.log_every == 0 or step == args.steps - 1:
                m = jax.device_get(metrics)
                dt = time.time() - t0
                print(
                    f"[train] step={step} loss={m['loss']:.4f} ce={m['ce']:.4f} "
                    f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                    f"({dt / max(step - start_step + 1, 1):.2f}s/step)",
                    flush=True,
                )
            if ckpt and step and step % args.ckpt_every == 0:
                ckpt.save_async(
                    step, state,
                    extra={"data": data.state_dict(), "train_step": step},
                )
        if ckpt:
            ckpt.save(
                args.steps, state,
                extra={"data": data.state_dict(), "train_step": args.steps},
            )
            ckpt.wait()
    data.stop()
    print("[train] done")


if __name__ == "__main__":
    main()
