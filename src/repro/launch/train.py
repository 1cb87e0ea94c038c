"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 50 --batch 8 --seq 128 [--robust] [--opt mu2|momentum|sgd]

Runs real steps on the available devices (CPU here; on TPU the same script
shards over the production mesh via --mesh). Checkpoints every
``--ckpt-every`` steps into --workdir.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs import ARCH_NAMES, get_config, smoke_config
from repro.data import lm_batches
from repro.dist.steps import (RobustDPConfig, init_train_state, make_robust_train_step,
                              make_train_step)
from repro.optim.mu2sgd import OptConfig
from repro.utils import enable_compile_cache, logger


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list(ARCH_NAMES))
    ap.add_argument("--smoke", action="store_true", help="reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--opt", default="mu2", choices=["mu2", "momentum", "sgd"])
    ap.add_argument("--robust", action="store_true")
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--agg", default="ctma:cwmed",
                    help="repro.agg spec: rule[:base][@backend], e.g. "
                         "ctma:gm@pallas | cwmed | zeno")
    ap.add_argument("--lam", type=float, default=0.25)
    ap.add_argument("--byz-groups", type=int, default=0)
    ap.add_argument("--byz-attack", default="sign_flip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt_cfg = OptConfig(name=args.opt, lr=args.lr, gamma=0.1, beta=0.25)
    robust_cfg = None
    if args.robust:
        byz = tuple(range(args.byz_groups))
        robust_cfg = RobustDPConfig(n_groups=args.groups, agg=args.agg, lam=args.lam,
                                    byz_groups=byz, byz_attack=args.byz_attack
                                    if byz else "none")
        step_fn = make_robust_train_step(cfg, opt_cfg, robust_cfg)
    else:
        step_fn = make_train_step(cfg, opt_cfg)
    # donate the train state: w/x/D buffers update in place every step
    step_fn = jax.jit(step_fn, donate_argnums=(0,))

    state = init_train_state(cfg, opt_cfg, jax.random.PRNGKey(args.seed), robust_cfg)
    data = lm_batches(cfg, args.batch, args.seq, seed=args.seed)

    losses = []
    t0 = time.time()
    for k in range(args.steps):
        state, metrics = step_fn(state, next(data))
        losses.append(float(metrics["loss"]))
        if args.log_every and (k + 1) % args.log_every == 0:
            logger.info("step %d/%d loss %.4f (%.2f s/step)", k + 1, args.steps,
                        losses[-1], (time.time() - t0) / (k + 1))
        if args.ckpt_every and args.workdir and (k + 1) % args.ckpt_every == 0:
            save_pytree(state.opt.w, Path(args.workdir) / "ckpt", k + 1)

    first = float(np.mean(losses[:5])) if len(losses) >= 5 else losses[0]
    last = float(np.mean(losses[-5:]))
    logger.info("done: loss %.4f -> %.4f over %d steps", first, last, args.steps)
    return {"first_loss": first, "last_loss": last, "losses": losses}


if __name__ == "__main__":
    main()
