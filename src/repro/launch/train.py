"""Training launcher: --arch <id> [--smoke] — end-to-end driver.

On the CPU container this runs reduced configs for real (examples/CI); on a
pod, the same entry point drives the full config with the production mesh
(single process per host, jax.distributed initialization left to the
scheduler environment).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer, latest_step
from repro.configs import get_config
from repro.data import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.runtime import Supervisor, SupervisorConfig
from repro.train import TrainLoopConfig, make_optimizer, train_loop


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    init_opt, _ = make_optimizer(args.optimizer, lr=args.lr)
    opt_state = init_opt(params)

    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if args.resume and ck is not None and latest_step(ck.directory) is not None:
        restored, start_step = ck.restore(
            {"params": params, "opt_state": opt_state})
        params, opt_state = restored["params"], restored["opt_state"]
        print(f"resumed from step {start_step}")

    pipe = TokenPipeline(cfg.vocab, args.seq, args.batch)

    def batches():
        step = start_step
        while True:
            t, lbl = pipe.batch_at(step)
            yield {"tokens": jnp.asarray(t), "labels": jnp.asarray(lbl)}
            step += 1

    lc = TrainLoopConfig(optimizer=args.optimizer, lr=args.lr,
                         max_steps=args.steps, compress=args.compress,
                         checkpoint_every=max(args.steps // 4, 1))

    sup = Supervisor(SupervisorConfig())

    def body(start):
        nonlocal params, opt_state
        params, opt_state, info = train_loop(
            cfg, lc, params, opt_state, batches(), checkpointer=ck,
            start_step=start)
        for step, loss in info["history"]:
            print(f"step {step:>5d} loss {loss:.4f}")
        print(f"{info['seconds']:.1f}s for {args.steps} steps")
        return args.steps

    sup.run(body, restore=lambda: start_step)


if __name__ == "__main__":
    main()
