"""Faults planted under the timed path, to show that the comparison catches
them.  Each is a context manager that patches the program for its body;
``FAULTS[plane]`` lists those a plane's cells can have.

- ``unchanged``: a round returns the model state it was given (on the LM
  plane with zero losses; ``unchanged_losses`` with each active worker's
  real loss at that state);
- ``half_batch``: each local step trains on half its minibatch (the second
  half repeats the first), so the mean is taken over the rest;
- ``plan_altered``: round 5 activates one worker more than the planner chose;
- ``loss_altered``: the sim's global-model eval loss, or the LM's training
  loss of each step, comes out 1% high;
- ``mix_altered`` (LM): Eq. 4 weights the puller's own model twice, each
  mixed row renormalized over the same pulled set.

The cells run on one chip, so there is no exchange between chips to leave
out.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name: str, value, retrace=()):
    """``obj.name = value`` for the body; the jitted functions in
    ``retrace`` drop their compiled programs on the way in and out, so
    that they trace the patched code."""
    old = obj.__dict__[name] if isinstance(obj, type) else getattr(obj, name)
    setattr(obj, name, value)
    for fn in retrace:
        fn.clear_cache()
    try:
        yield
    finally:
        setattr(obj, name, old)
        for fn in retrace:
            fn.clear_cache()


@contextlib.contextmanager
def unchanged():
    import jax.numpy as jnp
    from repro.dfl import worker as WK

    def same(buf, *args, **kw):
        return buf, jnp.zeros((buf.shape[0],), jnp.float32)

    with _patched(WK, "mega_round_step", same), \
            _patched(WK, "round_step", same):
        yield


@contextlib.contextmanager
def half_batch():
    import jax.numpy as jnp
    from repro.dfl import worker as WK
    orig = WK.sample_batches_device

    def half(*args, **kw):
        x, y = orig(*args, **kw)
        b = x.shape[2] // 2
        x = jnp.concatenate([x[:, :, :b], x[:, :, :x.shape[2] - b]], axis=2)
        y = jnp.concatenate([y[:, :, :b], y[:, :, :y.shape[2] - b]], axis=2)
        return x, y

    with _patched(WK, "sample_batches_device", half,
                  retrace=(WK.mega_round_step, WK.round_step)):
        yield


@contextlib.contextmanager
def plan_altered():
    from repro.core import planner as PL
    orig = PL.HorizonPlanner.plan_round

    def plan(self):
        p = orig(self)
        if p.t == 5:
            idle = (~p.active).nonzero()[0]
            if len(idle):
                p.active = p.active.copy()
                p.active[idle[0]] = True
        return p

    with _patched(PL.HorizonPlanner, "plan_round", plan):
        yield


@contextlib.contextmanager
def loss_altered():
    from repro.dfl import worker as WK
    orig = WK.evaluate_global_flat

    def ev(*args, **kw):
        acc, loss = orig(*args, **kw)
        return acc, loss * 1.01

    with _patched(WK, "evaluate_global_flat", ev):
        yield


@contextlib.contextmanager
def lm_unchanged():
    import numpy as np
    from repro.dfl import lm_worker as LW

    def same(self, pbuf, obuf, chunk, *args, **kw):
        return pbuf, obuf, np.zeros((len(chunk), pbuf.shape[0]), np.float32)

    with _patched(LW.LMEngine, "dispatch_chunk", same):
        yield


@contextlib.contextmanager
def lm_unchanged_losses():
    import numpy as np
    from repro.dfl import lm_worker as LW

    def same(self, pbuf, obuf, chunk, tokens, labels, **kw):
        n = pbuf.shape[0]
        losses = np.zeros((len(chunk), n), np.float32)
        for h, p in enumerate(chunk):
            for i in np.flatnonzero(p.active):
                alpha = np.zeros((n,), np.float32)
                alpha[i] = 1.0
                losses[h, i] = float(self.eval_global(
                    pbuf, alpha, tokens[h, i], labels[h, i]))
        return pbuf, obuf, losses

    with _patched(LW.LMEngine, "dispatch_chunk", same):
        yield


@contextlib.contextmanager
def lm_half_batch():
    from repro.dfl import lm_worker as LW
    orig = LW.LMEngine.dispatch_chunk

    def half(self, pbuf, obuf, chunk, tokens, labels, **kw):
        b = tokens.shape[2] // 2
        tokens, labels = tokens.copy(), labels.copy()
        tokens[:, :, b:2 * b] = tokens[:, :, :b]
        labels[:, :, b:2 * b] = labels[:, :, :b]
        return orig(self, pbuf, obuf, chunk, tokens, labels, **kw)

    with _patched(LW.LMEngine, "dispatch_chunk", half):
        yield


@contextlib.contextmanager
def lm_loss_altered():
    import jax.numpy as jnp
    from repro.dfl import lm_worker as LW
    orig = LW.LMEngine.dispatch_chunk

    def high(self, *args, **kw):
        pbuf, obuf, losses = orig(self, *args, **kw)
        return pbuf, obuf, jnp.asarray(losses) * 1.01

    with _patched(LW.LMEngine, "dispatch_chunk", high):
        yield


@contextlib.contextmanager
def lm_mix_altered():
    import dataclasses

    import numpy as np
    from repro.dfl import lm_worker as LW
    orig = LW.LMEngine.dispatch_chunk

    def skew(self, pbuf, obuf, chunk, *args, **kw):
        out = []
        for p in chunk:
            W = np.array(p.W, np.float64)
            for i in p.mix_rows:
                W[i, i] *= 2.0
                W[i] /= W[i].sum()
            out.append(dataclasses.replace(p, W=W.astype(p.W.dtype)))
        return orig(self, pbuf, obuf, out, *args, **kw)

    with _patched(LW.LMEngine, "dispatch_chunk", skew):
        yield


FAULTS = {
    "sim": {"unchanged": unchanged, "half_batch": half_batch,
            "plan_altered": plan_altered, "loss_altered": loss_altered},
    "lm": {"unchanged": lm_unchanged, "half_batch": lm_half_batch,
           "plan_altered": plan_altered, "loss_altered": lm_loss_altered,
           "unchanged_losses": lm_unchanged_losses,
           "mix_altered": lm_mix_altered},
}
