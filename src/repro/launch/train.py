"""Distributed training driver.

Production shape: pjit'd train step with the launch/sharding.py rules,
async checkpointing, restart-on-failure supervision, straggler monitoring,
and checkpointable data-iterator state. On the CPU container it runs the
reduced (--smoke) configs end-to-end on a host mesh; on a real cluster the
same entrypoint runs the full configs on make_production_mesh() (every
piece — shardings, steps, checkpoints — is mesh-agnostic).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.data import TokenStream
from repro.launch import sharding as shard_lib
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import build_model, param_count
from repro.optim import adamw, cosine_schedule
from repro.runtime import StragglerMonitor


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (requires 256 devices)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    mesh = (
        make_production_mesh() if args.production_mesh else make_host_mesh()
    )
    print(f"[train] arch={cfg.name} mesh={dict(mesh.shape)} "
          f"devices={mesh.devices.size}")

    opt = adamw(
        lr=cosine_schedule(args.lr, args.steps, args.warmup), weight_decay=0.1
    )

    # --- init (sharded via jit so large params materialize pre-sharded) ---
    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = shard_lib.params_shardings(mesh, p_shapes)
    o_shapes = jax.eval_shape(opt.init, p_shapes)
    o_shard = shard_lib.opt_shardings(mesh, o_shapes)

    with jax.set_mesh(mesh):
        params = jax.jit(model.init, out_shardings=p_shard)(
            jax.random.PRNGKey(0)
        )
        opt_state = jax.jit(opt.init, out_shardings=o_shard)(params)
    print(f"[train] params: {param_count(params):,}")

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    batch_sds = {
        "tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
    }
    b_shard = shard_lib.batch_shardings(mesh, batch_sds)
    step_fn = jax.jit(
        train_step,
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, shard_lib.replicated(mesh)),
        donate_argnums=(0, 1),
    )

    data = TokenStream(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch_size=args.batch,
        host_id=jax.process_index(), num_hosts=jax.process_count(),
    )
    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        payload, start = restore_checkpoint(
            args.ckpt_dir, {"params": params, "opt": opt_state,
                            "data_step": np.asarray(0)}
        )
        params, opt_state = payload["params"], payload["opt"]
        data.restore({"step": int(payload["data_step"])})
        print(f"[train] resumed from step {start}")

    monitor = StragglerMonitor()
    times = []
    with jax.set_mesh(mesh):
        for step in range(start, args.steps):
            t0 = time.perf_counter()
            batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
            params, opt_state, loss = step_fn(params, opt_state, batch)
            loss = float(loss)
            dt = time.perf_counter() - t0
            times.append(dt)
            monitor.observe(step, {jax.process_index(): dt})
            if step % args.log_every == 0 or step == args.steps - 1:
                tok_s = args.batch * args.seq / dt
                print(f"[train] step {step:5d}  loss {loss:8.4f}  "
                      f"{dt*1e3:7.1f} ms/step  {tok_s:9.0f} tok/s")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state,
                                     "data_step": np.asarray(data.step)})
    if ckpt:
        ckpt.save(args.steps, {"params": params, "opt": opt_state,
                               "data_step": np.asarray(data.step)})
        ckpt.wait()
    print(f"[train] done; median step {np.median(times)*1e3:.1f} ms")


if __name__ == "__main__":
    main()
