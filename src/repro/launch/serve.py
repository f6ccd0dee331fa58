"""Batched decode driver: prefill a prompt batch, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
        --batch 4 --prompt-len 32 --gen 32

With ``--from-ckpt`` the params come from a fleet checkpoint written by
``run_lm_federation`` instead of a fresh init — the Eq. 11 weighted global
model by default, or one worker's own model with ``--worker i``:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --from-ckpt ckpts/ckpt_round000010.npz --batch 4 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeSpec
from repro.data.synthetic import make_token_stream
from repro.models import registry as R
from repro.models import transformer as T
from repro.models import encdec as E
from repro.launch.cache import enable_compile_cache


def serve(arch: str, smoke: bool, batch: int, prompt_len: int, gen: int,
          max_len: int = 512, from_ckpt: str | None = None,
          worker: int | None = None):
    cfg = R.get_smoke_config(arch) if smoke else R.get_config(arch)
    key = jax.random.PRNGKey(0)
    if from_ckpt is not None:
        from repro.serving.bridge import serving_params_from_checkpoint
        params = serving_params_from_checkpoint(from_ckpt, cfg, worker=worker)
        src = f"ckpt={from_ckpt}" + ("" if worker is None
                                     else f" worker={worker}")
        print(f"loaded serving params from {src}")
    else:
        params, _ = R.init_params(cfg, key)
    shape = ShapeSpec("serve", max_len, batch, "decode")
    cache = R.init_decode_cache(cfg, shape)

    stream = make_token_stream(cfg.vocab_size, batch * prompt_len + 1)
    prompt = jnp.asarray(stream[:batch * prompt_len].reshape(batch, prompt_len))

    if R.is_encdec(cfg):
        frames = jax.random.normal(key, (batch, R.frames_for(cfg, max_len),
                                         cfg.d_model), jnp.dtype(cfg.dtype))
        cache = E.fill_cross_cache(cfg, params, cache, frames)
        _, cache = E_prefill(cfg, params, cache, prompt)
    else:
        _, cache = T.prefill_cache(cfg, params, cache, prompt)

    step = jax.jit(lambda p, c, t: R.serve_step(cfg, p, c, t))
    tok = prompt[:, -1:]
    out = [tok]
    t0 = time.time()
    for _ in range(gen):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits[:, -1:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
        out.append(tok)
    dt = (time.time() - t0) / gen
    seqs = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.arch_id} batch={batch} {dt*1e3:.1f} ms/token")
    for b in range(min(batch, 2)):
        print(f"  sample[{b}]: {np.asarray(seqs[b])[:16].tolist()} ...")
    return seqs


def E_prefill(cfg, params, cache, prompt):
    def step(c, tok):
        logits, c = E.decode_step(cfg, params, c, tok[:, None])
        return c, logits[:, 0]

    cache, logits = jax.lax.scan(step, cache, jnp.moveaxis(prompt, 1, 0))
    return jnp.moveaxis(logits, 0, 1), cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=R.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--from-ckpt", default=None,
                    help="fleet checkpoint (.npz) to serve from; default is "
                         "the Eq. 11 weighted global model")
    ap.add_argument("--worker", type=int, default=None,
                    help="serve worker i's own model instead of the global")
    args = ap.parse_args()
    serve(args.arch, args.smoke, args.batch, args.prompt_len, args.gen,
          from_ckpt=args.from_ckpt, worker=args.worker)


if __name__ == "__main__":
    main()
