"""Training launcher: checkpoint/restart, heartbeat + straggler deadline,
elastic resume, optional int8 gradient compression.

Runs the real thing on whatever devices exist (1 CPU device in this
container; the same code path jits under the production mesh via
``--mesh production``).  Fault-tolerance model:

  * atomic checkpoints every ``--ckpt-every`` steps (async writer)
  * on start, resumes from the latest complete checkpoint (crash = rerun)
  * per-step heartbeat wall-time log; steps exceeding ``--step-deadline``
    raise a straggler event -> checkpoint immediately and (in production)
    signal the controller to reslice; here it is logged and survivable
  * elastic: the data pipeline derives batches from (seed, step) and
    checkpoints store logical arrays, so a resumed run may use a different
    device count / mesh — restore reshards

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt_lib
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.steps import make_train_step
from repro.models import lm
from repro.optim import AdamWConfig, adamw_init, compress_init


def build(arch: str, *, smoke: bool, seq: int, batch: int, sqrt_unit: str,
          microbatches: int, compress: bool, opt_overrides=None):
    """Assemble one training run: config, initialized params/optimizer, the
    jitted (donating) train step and a synthetic data source.  Returns
    ``(cfg, params, opt_state, step_fn, data)`` — the pieces
    :func:`train_loop` iterates, reusable for custom loops."""
    cfg = (get_smoke_config if smoke else get_config)(arch, sqrt_unit=sqrt_unit)
    params, specs = lm.init(cfg, jax.random.key(0))
    # the optimizer steps fp32 master weights; the forward casts them to
    # the activation dtype at each use
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    opt_cfg = AdamWConfig(sqrt_unit=sqrt_unit, **(opt_overrides or {}))
    opt_state = adamw_init(params)
    if compress:
        opt_state["residual"] = compress_init(params)
    step_fn = jax.jit(
        make_train_step(cfg, opt_cfg, compress_grads=compress, microbatches=microbatches),
        donate_argnums=(0, 1),
    )
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch))
    return cfg, params, opt_state, step_fn, data


def train_loop(arch="qwen3-4b", *, smoke=True, steps=20, seq=64, batch=4,
               sqrt_unit="e2afs", ckpt_dir=None, ckpt_every=10, microbatches=1,
               compress=False, step_deadline=None, log_every=5,
               inject_straggler_at=None, lr=None, abort_after=None):
    """Run ``steps`` of training end to end (synthetic LM data), with the
    approximate sqrt unit live in every norm and the optimizer.  Optional
    production machinery: periodic async checkpointing to ``ckpt_dir`` with
    resume-from-latest, a per-step wall-clock ``step_deadline`` (straggler
    detection; ``inject_straggler_at`` simulates one for tests), gradient
    compression, and microbatched accumulation.  Returns
    ``(params, opt_state, losses)``."""
    opt_overrides = {
        "lr": lr if lr is not None else (3e-3 if smoke else 3e-4),
        "warmup_steps": max(2, steps // 10),
        "total_steps": steps,
    }
    cfg, params, opt_state, step_fn, data = build(
        arch, smoke=smoke, seq=seq, batch=batch, sqrt_unit=sqrt_unit,
        microbatches=microbatches, compress=compress, opt_overrides=opt_overrides,
    )

    start = 0
    if ckpt_dir:
        latest = ckpt_lib.latest_step(ckpt_dir)
        if latest is not None:
            state = ckpt_lib.restore(
                ckpt_dir, latest, {"params": params, "opt": opt_state}
            )
            params, opt_state = state["params"], state["opt"]
            start = latest
            print(f"[restore] resumed from step {latest}")

    heartbeat = []
    losses = []
    for step in range(start, steps):
        batch_np = data.batch(step)
        batch_jx = {k: jnp.asarray(v) for k, v in batch_np.items()}
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, batch_jx)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        heartbeat.append({"step": step, "wall_s": dt, "loss": loss})
        losses.append(loss)

        straggled = (step_deadline and dt > step_deadline) or (
            inject_straggler_at is not None and step == inject_straggler_at
        )
        if straggled:
            print(f"[straggler] step {step} took {dt:.2f}s > deadline; "
                  "checkpointing for reslice")
            if ckpt_dir:
                ckpt_lib.save(ckpt_dir, step + 1, {"params": params, "opt": opt_state})
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ckpt_lib.save_async(ckpt_dir, step + 1, {"params": params, "opt": opt_state})
        if (step + 1) % log_every == 0:
            print(f"  step {step + 1:5d} loss {loss:.4f} ({dt * 1e3:.0f} ms)")
        if abort_after is not None and step + 1 >= abort_after:
            # simulated crash: no final checkpoint beyond what ckpt_every wrote
            ckpt_lib.wait_pending()
            return params, opt_state, losses

    ckpt_lib.wait_pending()
    if ckpt_dir:
        ckpt_lib.save(ckpt_dir, steps, {"params": params, "opt": opt_state})
        Path(ckpt_dir, "heartbeat.json").write_text(json.dumps(heartbeat))
    return params, opt_state, losses


def main():
    """CLI wrapper over :func:`train_loop`:
    ``python -m repro.launch.train [--arch qwen3-4b] [--steps N] ...``"""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--sqrt-unit", default="e2afs")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--step-deadline", type=float, default=None)
    args = ap.parse_args()
    _, _, losses = train_loop(
        args.arch, smoke=args.smoke, steps=args.steps, seq=args.seq,
        batch=args.batch, sqrt_unit=args.sqrt_unit, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, microbatches=args.microbatches,
        compress=args.compress_grads, step_deadline=args.step_deadline,
    )
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")


if __name__ == "__main__":
    main()
