"""Training entry point: ``python -m repro.launch.train --arch qwen3-14b
--smoke --steps 200``.

Trains an assigned architecture with MGD (or the backprop baseline) on the
synthetic LM stream.  ``--smoke`` selects the reduced config (CPU-runnable);
without it the published config is built as is.
Checkpoints are atomic and resumable (--ckpt-dir); a killed run restarted
with the same flags reproduces the exact trajectory.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.core import MGDConfig
from repro.data.pipeline import lm_sampler
from repro.launch.cache import use_compile_cache
from repro.models import model_init, model_loss
from repro.training.train_loop import (TrainLoopConfig, train_backprop,
                                       train_mgd)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--algo", default="mgd", choices=["mgd", "backprop"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--eta", type=float, default=None)
    ap.add_argument("--dtheta", type=float, default=1e-2)
    ap.add_argument("--tau-theta", type=int, default=1)
    ap.add_argument("--tau-x", type=int, default=1)
    ap.add_argument("--mode", default="central",
                    choices=["forward", "central"])
    ap.add_argument("--probes", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=20)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = model_init(cfg, jax.random.PRNGKey(args.seed))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    print(f"[train] {cfg.name} ({'smoke' if args.smoke else 'full'}): "
          f"{n/1e6:.2f}M params, algo={args.algo}")

    sample_fn = lm_sampler(args.batch, args.seq, cfg.vocab, seed=args.seed)
    loss_fn = lambda p, b: model_loss(p, cfg, b)      # noqa: E731

    if args.algo == "mgd":
        eta = args.eta if args.eta is not None else 1e-2
        mgd_cfg = MGDConfig(
            ptype="rademacher", dtheta=args.dtheta, eta=eta,
            tau_theta=args.tau_theta, tau_x=args.tau_x, mode=args.mode,
            probes=args.probes, seed=args.seed)
        res = train_mgd(loss_fn, params, mgd_cfg, sample_fn, args.steps,
                        loop=TrainLoopConfig(
                            chunk=args.chunk, checkpoint_dir=args.ckpt_dir,
                            checkpoint_every=args.ckpt_every))
    else:
        eta = args.eta if args.eta is not None else 0.3
        res = train_backprop(loss_fn, params, sample_fn, args.steps,
                             eta=eta, chunk=args.chunk)
    first = res.history[0][1]["cost"]
    last = res.history[-1][1]["cost"]
    print(f"[train] done: cost {first:.4f} → {last:.4f} "
          f"over {res.steps_done} steps")


if __name__ == "__main__":
    main()
