"""End-to-end LM training driver (single DFL worker's local plane).

Trains any registry architecture on the synthetic token stream with the same
pjit train step the dry-run lowers, on whatever devices exist (1-device mesh
on the CPU container; the production mesh on a real pod).  Supports smoke
(--smoke) geometry for fast runs and periodic checkpointing.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
        --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.data.synthetic import lm_batches, make_token_stream
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh
from repro.models import registry as R
from repro.optim import get_optimizer
from repro.sharding.rules import use_sharding_rules
from repro.launch.cache import enable_compile_cache


def train(arch: str, smoke: bool, steps: int, batch: int, seq: int, lr: float,
          optimizer: str, ckpt_path: str | None, log_every: int = 10):
    cfg = R.get_smoke_config(arch) if smoke else R.get_config(arch)
    mesh = make_host_mesh()
    opt = get_optimizer(optimizer, lr)

    key = jax.random.PRNGKey(0)
    params, _ = R.init_params(cfg, key)
    opt_state = opt.init(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.arch_id} params={n_params/1e6:.1f}M "
          f"optimizer={optimizer} lr={lr}")

    stream = make_token_stream(cfg.vocab_size, max(200_000, batch * seq * 4))
    batches = lm_batches(stream, batch, seq)

    step_fn = jax.jit(S.make_train_step(cfg, opt, remat=False))

    def adapt(b):
        out = {k: jnp.asarray(v) for k, v in b.items()}
        if R.is_encdec(cfg):
            out["frames"] = jnp.zeros(
                (batch, R.frames_for(cfg, seq), cfg.d_model), jnp.dtype(cfg.dtype))
        if R.has_prefix(cfg):
            p = min(cfg.n_prefix_tokens, seq // 2)
            # smoke prefix: random embeddings standing in for the stub frontend
            out["prefix_embeds"] = jax.random.normal(
                jax.random.PRNGKey(1), (batch, cfg.n_prefix_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        return out

    losses = []
    t0 = time.time()
    with mesh, use_sharding_rules(mesh):
        for i in range(1, steps + 1):
            b = adapt(next(batches))
            params, opt_state, metrics = step_fn(params, opt_state, b)
            losses.append(float(metrics["loss"]))
            if i % log_every == 0 or i == steps:
                dt = (time.time() - t0) / i
                print(f"step {i:5d} loss {losses[-1]:.4f} "
                      f"(avg last10 {np.mean(losses[-10:]):.4f}) {dt:.2f}s/step")
    if ckpt_path:
        save_checkpoint(ckpt_path, params, opt_state,
                        extra={"arch": cfg.arch_id, "steps": steps,
                               "final_loss": losses[-1]})
        print(f"checkpoint -> {ckpt_path}")
    return losses


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=R.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    losses = train(args.arch, args.smoke, args.steps, args.batch, args.seq,
                   args.lr, args.optimizer, args.ckpt)
    print(f"loss: first10 {np.mean(losses[:10]):.4f} -> "
          f"last10 {np.mean(losses[-10:]):.4f}")


if __name__ == "__main__":
    main()
