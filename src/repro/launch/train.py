"""Training launcher.

Local mode (default): runs a reduced config on the host devices — the
end-to-end driver used by examples/quickstart.py.  Production mode
(--production) builds the 16x16 (or 2x16x16) mesh shardings exactly as the
dry-run does; on real TPU hardware the same entry point drives the full
model (the only difference between dry-run and launch is .compile() vs
dispatch).

Fault tolerance:
  * coded checkpoints every --ckpt-every steps (async, RS parity across
    --ckpt-shards with --ckpt-parity tolerance) — restart with --resume
  * simulated failure injection (--fail-at step,shard[,shard...]) exercises
    the reconstruct path end-to-end
  * straggler-tolerant gradient coding (--stragglers s): the batch is cut
    across --coded-workers per the fractional-repetition assignment and
    every step decodes around the injected straggler mask
    (--straggler-mode random|bursty|fixed) with bitwise-exact gradients —
    --straggler-selfcheck asserts that against the all-alive step
"""
from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--peak-lr", type=float, default=3e-3)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full-config", dest="smoke", action="store_false")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override smoke width (e.g. 512 for a ~100M model)")
    ap.add_argument("--n-layers", type=int, default=0)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-shards", type=int, default=16)
    ap.add_argument("--ckpt-parity", type=int, default=4)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", default=None,
                    help="step,shard[,shard...]: simulate node failures")
    ap.add_argument("--stragglers", type=int, default=0,
                    help="s > 0: gradient-coded step tolerating s "
                         "stragglers per step (requires (s+1) | workers)")
    ap.add_argument("--coded-workers", type=int, default=8,
                    help="data-parallel workers for --stragglers "
                         "(batch must divide evenly)")
    ap.add_argument("--straggler-mode", default="random",
                    choices=["random", "bursty", "fixed"])
    ap.add_argument("--straggler-rate", type=float, default=0.5)
    ap.add_argument("--straggler-seed", type=int, default=0)
    ap.add_argument("--straggler-selfcheck", action="store_true",
                    help="assert bitwise gradient recovery vs the "
                         "all-alive step before training")
    ap.add_argument("--production", action="store_true",
                    help="use the 16x16 production mesh shardings")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.production:
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=512 "
            + os.environ.get("XLA_FLAGS", ""))

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ckpt import CodedCheckpointer
    from ..coding import GradientCoder
    from ..compile_cache import enable_compile_cache
    from ..configs import get_config
    from ..data import SyntheticLM
    from ..train import (StragglerInjector, init_state,
                         make_straggler_train_step, make_train_setup,
                         make_train_step)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.d_model:
        cfg = dataclasses.replace(
            cfg, d_model=args.d_model, d_ff=args.d_model * 3,
            head_dim=max(args.d_model // max(cfg.n_heads, 1), 8))
    if args.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)

    opt, lr = make_train_setup(cfg, total_steps=args.steps, peak_lr=args.peak_lr)
    state = init_state(cfg, jax.random.PRNGKey(0), opt)
    from ..models.model import param_count
    print(f"arch={cfg.name} params={param_count(state.params):,} "
          f"devices={jax.device_count()}")

    ckpt = None
    if args.ckpt_dir:
        ckpt = CodedCheckpointer(args.ckpt_dir, args.ckpt_shards, args.ckpt_parity)
        if args.resume and ckpt.latest_step() is not None:
            s = ckpt.latest_step()
            state = ckpt.restore(s, state)
            print(f"resumed from coded checkpoint step {s}")

    fail_step, fail_shards = -1, set()
    if args.fail_at:
        parts = [int(x) for x in args.fail_at.split(",")]
        fail_step, fail_shards = parts[0], set(parts[1:])

    data = SyntheticLM(cfg.vocab, args.seq_len, args.batch)
    straggle = None
    if args.stragglers > 0:
        coder = GradientCoder(args.coded_workers, s=args.stragglers)
        if args.batch % coder.n_workers:
            raise SystemExit(f"--batch {args.batch} must be divisible by "
                             f"--coded-workers {coder.n_workers}")
        coded_fn = make_straggler_train_step(cfg, opt, coder)
        straggle = StragglerInjector.build(
            args.straggler_mode, coder, args.steps,
            rate=args.straggler_rate, seed=args.straggler_seed)
        print(f"gradient coding: {coder.n_workers} workers, "
              f"s={coder.s} tolerated, {coder.n_groups} groups, "
              f"{args.straggler_mode} stragglers "
              f"({len(straggle.plan)} worker-step straggles planned)")
        if args.straggler_selfcheck:
            b0 = data.device_batch(0)
            mask = straggle.mask(0)
            if mask.all():  # make the check exercise a real straggle
                mask[:args.stragglers] = False
            s_dead, _ = coded_fn(state, b0, mask)
            s_live, _ = coded_fn(state, b0)
            leaves_a = jax.tree.leaves(s_dead.params)
            leaves_b = jax.tree.leaves(s_live.params)
            assert all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(leaves_a, leaves_b)), \
                "straggler step diverged from all-alive step"
            print(f"selfcheck OK: step with stragglers "
                  f"{[int(w) for w in np.flatnonzero(~mask)]} "
                  "bitwise == all-alive")

        def step_fn(st, batch, i):
            return coded_fn(st, batch, straggle.mask(i))
    else:
        base_fn = jax.jit(make_train_step(cfg, opt, args.microbatches,
                                          args.compress_grads))

        def step_fn(st, batch, i):
            return base_fn(st, batch)

    t0 = time.time()
    start = int(state.step)
    straggled = 0
    for i in range(start, args.steps):
        state, metrics = step_fn(state, data.device_batch(i), i)
        straggled += int(metrics.get("stragglers", 0))
        if ckpt and (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, jax.device_get(state), background=True)
        if i == fail_step:
            print(f"!! simulating failure of shards {fail_shards} at step {i}")
            ckpt.wait()
            s = ckpt.latest_step()
            state = ckpt.restore(s, state, failed_shards=fail_shards)
            print(f"   reconstructed from parity; resumed at step {s}")
        if (i + 1) % args.log_every == 0 or i == start:
            dt = (time.time() - t0) / (i - start + 1)
            print(f"step {i + 1:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(lr(jnp.int32(i))):.2e} {dt * 1e3:.0f} ms/step",
                  flush=True)
    if ckpt:
        ckpt.save(args.steps, jax.device_get(state))
        ckpt.wait()
    if straggle is not None:
        print(f"stragglers: {straggled} worker-steps decoded around "
              f"({args.straggler_mode}, s={args.stragglers})")
    print(f"done: final loss {float(metrics['loss']):.4f}")
    return state


if __name__ == "__main__":
    main()
