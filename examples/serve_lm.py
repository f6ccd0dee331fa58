"""End-to-end LM path: briefly train a smoke-geometry architecture on the
synthetic token stream, checkpoint it, reload, and serve greedy decodes with
the production decode step (ring-buffer KV caches for local-attention layers).

    PYTHONPATH=src python examples/serve_lm.py --arch gemma2-2b
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs.base import ShapeSpec
from repro.data.synthetic import lm_batches, make_token_stream
from repro.launch import steps as S
from repro.models import registry as R
from repro.models import transformer as T
from repro.optim import get_optimizer
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=R.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()

    cfg = R.get_smoke_config(args.arch)
    if R.is_encdec(cfg) or R.has_prefix(cfg):
        raise SystemExit("pick a decoder-only arch for this example")

    opt = get_optimizer("adam", 1e-3)
    params, _ = R.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    step_fn = jax.jit(S.make_train_step(cfg, opt, remat=False))
    batches = lm_batches(make_token_stream(cfg.vocab_size, 100_000), 8, 64)

    for i in range(1, args.steps + 1):
        b = {k: jnp.asarray(v) for k, v in next(batches).items()}
        params, opt_state, m = step_fn(params, opt_state, b)
        if i % 10 == 0:
            print(f"train step {i}: loss {float(m['loss']):.4f}")

    with tempfile.NamedTemporaryFile(suffix=".npz") as f:
        save_checkpoint(f.name, params)
        params, _, _ = load_checkpoint(f.name, params)
        print("checkpoint round-trip OK")

    # serve
    shape = ShapeSpec("serve", 128, 2, "decode")
    cache = R.init_decode_cache(cfg, shape)
    prompt = jnp.asarray(next(batches)["tokens"][:2, :16])
    _, cache = T.prefill_cache(cfg, params, cache, prompt)
    step = jax.jit(lambda p, c, t: R.serve_step(cfg, p, c, t))
    tok, out = prompt[:, -1:], []
    for _ in range(24):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits[:, -1:, :cfg.vocab_size], -1).astype(jnp.int32)
        out.append(int(tok[0, 0]))
    print(f"greedy continuation: {out}")


if __name__ == "__main__":
    main()
