#!/usr/bin/env python3
"""Faults of the ``lm_ssm`` plane (the Mamba-2 cells): the lm plane's, and
two that a batch of one long sequence and the chunked SSD call for.

- ``half_seq``: the second half of each sequence repeats the first (at a
  batch of one, ``half_batch`` plants nothing);
- ``state_dropped``: the SSD state is not carried across chunk boundaries
  (the inter-chunk scan hands every chunk a zero state), so each chunk of
  256 tokens sees only its own.

Importing the module adds ``faults.FAULTS["lm_ssm"]``.  Run as a script it
is ``calibrate.py`` with these faults, taking the same arguments:

    python3 chipbench/faults_ssm.py --workload lm-mamba2l4-n2 --rounds 16 \
        --seeds <s1> <s2> ... [--control 3] [--faults 2]
"""
from __future__ import annotations

import contextlib
import pathlib
import sys
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import faults as FA  # noqa: E402


@contextlib.contextmanager
def half_seq():
    from repro.dfl import lm_worker as LW
    orig = LW.LMEngine.dispatch_chunk

    def half(self, pbuf, obuf, chunk, tokens, labels, **kw):
        s = tokens.shape[-1] // 2
        tokens, labels = tokens.copy(), labels.copy()
        tokens[..., s:2 * s] = tokens[..., :s]
        labels[..., s:2 * s] = labels[..., :s]
        return orig(self, pbuf, obuf, chunk, tokens, labels, **kw)

    with FA._patched(LW.LMEngine, "dispatch_chunk", half):
        yield


@contextlib.contextmanager
def state_dropped():
    import jax
    import jax.numpy as jnp
    from repro.dfl import lm_worker as LW
    from repro.models import ssm as S

    def scan(f, init, xs, *args, **kw):
        last, states = jax.lax.scan(f, init, xs, *args, **kw)
        return last, jnp.zeros_like(states)

    # models/ssm.py's one scan is the inter-chunk one; the engines are
    # dropped on the way in and out so that their programs trace it anew
    lax = types.SimpleNamespace(**dict(vars(jax.lax), scan=scan))
    proxy = types.SimpleNamespace(**dict(vars(jax), lax=lax))
    LW._ENGINE_CACHE.clear()
    try:
        with FA._patched(S, "jax", proxy):
            yield
    finally:
        LW._ENGINE_CACHE.clear()


FA.FAULTS["lm_ssm"] = dict(FA.FAULTS["lm"], half_seq=half_seq,
                           state_dropped=state_dropped)


if __name__ == "__main__":
    import calibrate
    sys.exit(calibrate.main())
