"""Training driver with the fault-tolerance loop (deliverable (b) + DESIGN §6).

Features exercised end-to-end here:
  * resume-from-latest checkpoint (atomic manager; data-pipeline position
    rides in the manifest, so batch order is restart-invariant);
  * async checkpointing every --ckpt-every steps (I/O overlaps compute);
  * step-time EMA watchdog (straggler mitigation: a stalled step beyond
    k-sigma is logged and, with --watchdog-abort, exits non-zero so the
    cluster supervisor restarts the job from the last checkpoint);
  * microbatch gradient accumulation, remat, optional gradient compression;
  * elastic resume: checkpoints are mesh-agnostic host arrays, so
    --data-par/--model-par may differ across restarts.

CPU-scale usage (examples/train_lm.py wraps this):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, get_reduced_config
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.distributed.sharding import resolve_spec
from repro.launch.mesh import make_local_mesh
from repro.models import params as pr
from repro.models.registry import build_model, input_arrays
from repro.train.optim import OptConfig, init_opt_state
from repro.train.train_step import make_train_step
from jax.sharding import NamedSharding


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the arch's reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--watchdog-sigma", type=float, default=6.0)
    ap.add_argument("--watchdog-abort", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-pattern", default="markov",
                    choices=["uniform", "markov"])
    ap.add_argument("--override", action="append", default=[],
                    help="config overrides, e.g. --override num_layers=8 "
                         "--override d_model=512")
    args = ap.parse_args(argv)

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if args.override:
        import dataclasses
        kv = {}
        for ov in args.override:
            k, v = ov.split("=", 1)
            cur = getattr(cfg, k)
            kv[k] = type(cur)(v) if not isinstance(cur, bool) else v == "True"
        cfg = dataclasses.replace(cfg, **kv)
    model = build_model(cfg)
    mesh = make_local_mesh(args.data_par, args.model_par)

    opt_cfg = OptConfig(lr=args.lr, warmup_steps=args.warmup,
                        total_steps=args.steps,
                        compression=args.compression)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed,
                                  pattern=args.data_pattern))

    # --- init or resume ------------------------------------------------------
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    params = model.init(jax.random.PRNGKey(args.seed))
    opt_state = init_opt_state(params, opt_cfg)
    start_step = 0
    if mgr and args.resume and mgr.latest_step() is not None:
        step = mgr.latest_step()
        (state, extra) = mgr.restore(step, {"params": params,
                                            "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        data.restore(extra["data"])
        start_step = extra["train_step"]
        print(f"[resume] from checkpoint step {step} "
              f"(train step {start_step})", flush=True)

    with jax.sharding.set_mesh(mesh):
        step_fn = jax.jit(make_train_step(
            model, cfg, opt_cfg, remat=args.remat,
            microbatches=args.microbatches))

        pf = Prefetcher(data, depth=2)
        ema, emvar = None, 0.0
        t_train0 = time.time()
        losses = []
        try:
            for step in range(start_step, args.steps):
                t0 = time.time()
                batch = {k: jnp.asarray(v) for k, v in pf.next_batch().items()}
                if cfg.family == "audio":
                    rngf = np.random.default_rng(step)
                    batch["frames"] = jnp.asarray(
                        rngf.normal(size=(args.batch, cfg.encoder_seq,
                                          cfg.d_model)) * 0.02, cfg.dtype)
                params, opt_state, metrics = step_fn(params, opt_state, batch)
                loss = float(metrics["loss"])
                losses.append(loss)
                dt = time.time() - t0

                # --- straggler watchdog (EMA + k-sigma) --------------------
                if ema is None:
                    ema = dt
                else:
                    dev = dt - ema
                    thresh = ema + args.watchdog_sigma * max(emvar ** 0.5,
                                                             0.1 * ema)
                    if step > start_step + 5 and dt > thresh:
                        print(f"[watchdog] step {step} took {dt:.2f}s "
                              f"(ema {ema:.2f}s, thresh {thresh:.2f}s)",
                              flush=True)
                        if args.watchdog_abort:
                            if mgr:
                                mgr.save(step, {"params": params,
                                                "opt": opt_state},
                                         extra={"data": data.state(),
                                                "train_step": step + 1})
                            return 42          # supervisor restarts us
                    ema = 0.9 * ema + 0.1 * dt
                    emvar = 0.9 * emvar + 0.1 * dev * dev

                if step % args.log_every == 0 or step == args.steps - 1:
                    print(f"step {step:5d} loss {loss:.4f} "
                          f"aux {float(metrics['aux_loss']):.4f} "
                          f"{dt:.2f}s/step", flush=True)
                if mgr and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                    mgr.save(step + 1, {"params": params, "opt": opt_state},
                             extra={"data": data.state(),
                                    "train_step": step + 1},
                             blocking=False)     # async writer
        finally:
            pf.close()

        if mgr:
            mgr.save(args.steps, {"params": params, "opt": opt_state},
                     extra={"data": data.state(), "train_step": args.steps})
            mgr.wait()
        n = pr.param_count(model.specs())
        dt_all = time.time() - t_train0
        print(f"[done] {args.steps - start_step} steps, {n/1e6:.1f}M params, "
              f"{dt_all:.1f}s total; loss {losses[0]:.4f} -> {losses[-1]:.4f}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
