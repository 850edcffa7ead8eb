"""Fault-tolerant training driver.

Composes the substrate: token pipeline (step-indexed, bitwise resumable),
train_step (grad accumulation + remat + AdamW), checkpoint manager
(atomic, rotated, async), straggler monitor, and preemption handler.
Works at smoke scale on one CPU device and unchanged on a production mesh
(pass `mesh` + shardings — the dry-run proves those compile).

CLI:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 30 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import get_config, get_smoke_config
from repro.data.tokens import TokenStream
from repro.launch import steps as ST
from repro.models import lm
from repro.optim import AdamConfig
from repro.runtime import PreemptionHandler, StepMonitor


def train_loop(cfg, *, steps: int, global_batch: int, seq_len: int,
               ckpt_dir: str | None = None, save_every: int = 50,
               log_every: int = 10, lr: float = 3e-4, seed: int = 0,
               mesh=None, resume: bool = True, accum: int = 1,
               deadline_s: float | None = None, verbose: bool = True):
    from repro.launch.mesh import make_mesh

    mesh = mesh or make_mesh((1, 1), ("data", "model"))
    dp_axes = tuple(n for n in mesh.axis_names if n != "model")
    ctx = lm.ModelCtx(mesh=mesh, dp_axes=dp_axes,
                      tp_size=mesh.shape["model"],
                      dp_size=int(np.prod([mesh.shape[a] for a in dp_axes])),
                      qc_train=min(1024, seq_len),
                      gla_chunk=min(256, seq_len))
    opt_cfg = AdamConfig(lr=lr, weight_decay=0.01, compress=cfg.opt_compress)
    params, opt_state = ST.init_train_state(cfg, jax.random.PRNGKey(seed),
                                            opt_cfg)
    stream = TokenStream(cfg.vocab, seq_len, global_batch, seed=seed + 1)

    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if manager and resume and manager.latest_step() is not None:
        state, meta = manager.restore({"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start_step = int(meta["step"])
        if verbose:
            print(f"resumed from step {start_step}", flush=True)

    step_fn = jax.jit(ST.make_train_step(cfg, ctx, accum=accum,
                                         opt_cfg=opt_cfg),
                      donate_argnums=(0, 1))
    monitor = StepMonitor(deadline_s=deadline_s)
    preempt = PreemptionHandler()
    history = []
    try:
        with mesh:
            for step in range(start_step, steps):
                monitor.start_step()
                batch = {k: jnp.asarray(v)
                         for k, v in stream.batch(step).items()}
                if cfg.encoder_layers:
                    batch["enc_inputs"] = 0.05 * jax.random.normal(
                        jax.random.PRNGKey(step),
                        (global_batch, cfg.encoder_seq, cfg.d_model))
                params, opt_state, metrics = step_fn(params, opt_state, batch)
                loss = float(metrics["loss"])
                stats = monitor.end_step()
                history.append({"step": step + 1, "loss": loss, **stats})
                if verbose and (step + 1) % log_every == 0:
                    print(f"step {step+1:5d} loss {loss:.4f} "
                          f"({stats['step_time_s']:.2f}s"
                          f"{' STRAGGLER' if stats['straggler'] else ''})",
                          flush=True)
                if stats["escalate"] and verbose:
                    print("straggler escalation: recommend checkpoint + "
                          "reschedule", flush=True)
                want_save = manager and ((step + 1) % save_every == 0
                                         or step + 1 == steps
                                         or preempt.requested)
                if want_save:
                    manager.save(step + 1,
                                 {"params": params, "opt": opt_state},
                                 background=False)
                if preempt.requested:
                    if verbose:
                        print(f"preemption: checkpointed at {step+1}, "
                              "exiting cleanly", flush=True)
                    break
    finally:
        preempt.restore()
        if manager:
            manager.wait()
    return params, opt_state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    _, _, hist = train_loop(cfg, steps=args.steps, global_batch=args.batch,
                            seq_len=args.seq, ckpt_dir=args.ckpt,
                            accum=args.accum, lr=args.lr)
    print(f"first loss {hist[0]['loss']:.4f} -> last {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
