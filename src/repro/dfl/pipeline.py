"""Double-buffered host↔device dispatch pipeline.

JAX dispatch is asynchronous: a donated ``mega_round_step`` /
``LMEngine._mega`` call returns immediately with futures while XLA executes
in the background.  ``DispatchPipeline`` makes the overlap explicit and
BOUNDED: the drive loop ``submit()``s each in-flight chunk's output arrays,
and the pipeline blocks only when more than ``depth`` chunks are
outstanding — so while the device executes horizon chunk H, the host plans,
packs (``worker.pack_chunk``) and stages (one fused non-blocking
``jax.device_put``) chunk H+1.  ``depth`` is at least 1; depth 1 is classic
double buffering, the default on both planes.

Values are untouched: the pipeline never reorders dispatches, and every
read-back boundary — eval, snapshot, scenario event, end of run — calls
``drain()`` first, so ``save_snapshot`` reads a round-consistent buffer and
resume stays bit-identical at any depth (pinned by tests/test_pipeline.py
and scripts/chaos_check.py).

Every block is a ``drain`` span of the call's ``core.trace.Trace``
(back-pressure inside ``submit``, counted as ``backpressure_waits``, and
boundary drains): host time spent waiting on the device, which is not the
device's execute time — that overlaps the host's other spans, and only the
profiler's device trace gives it.
"""
from __future__ import annotations

from collections import deque
from typing import Any

import jax


def count_dispatch(trace, rounds: int, k_mix: int, k_train: int,
                   h2d_bytes: int = 0, narrow_mix: bool = False) -> None:
    """The counters of one dispatched chunk of ``rounds`` rounds, into the
    call's ``core.trace.Trace``: its mix and train rows as dispatched
    (``k_mix`` / ``k_train`` a round, bucket padding included), its rounds
    whose Eq. 4 mix ran the panel kernel's VPU body (``narrow_mix``, from
    ``worker.narrow_mix``) and the bytes staged on the device for it."""
    trace.count("dispatches")
    trace.count("scan_dispatches", rounds > 1)
    trace.count("mix_rows", rounds * k_mix)
    trace.count("mix_narrow_rounds", rounds if narrow_mix else 0)
    trace.count("train_rows", rounds * k_train)
    trace.count("h2d_bytes", h2d_bytes)


class DispatchPipeline:
    """Bounded queue of in-flight device dispatches (see module docstring)."""

    def __init__(self, depth: int, trace):
        if depth < 1:
            raise ValueError(f"DispatchPipeline depth must be >= 1, got "
                             f"{depth}")
        self.depth = int(depth)
        self.trace = trace
        self._inflight: deque = deque()

    def submit(self, token: Any) -> None:
        """Register one dispatched chunk's output (any jax array/pytree);
        blocks the OLDEST in-flight chunk(s) once more than ``depth`` are
        outstanding — back-pressure, so host plan-ahead stays bounded and
        donated buffers cannot pile up."""
        self._inflight.append(token)
        if len(self._inflight) > self.depth:
            self.trace.count("backpressure_waits")
            with self.trace.span("drain"):
                while len(self._inflight) > self.depth:
                    jax.block_until_ready(self._inflight.popleft())

    def drain(self) -> None:
        """Block until every in-flight chunk has executed.  Called at every
        read-back boundary (eval / snapshot / scenario event / end of run):
        after a drain the resident buffers are round-consistent and host
        reads charge no device time to the wrong phase."""
        if not self._inflight:
            return
        with self.trace.span("drain"):
            while self._inflight:
                jax.block_until_ready(self._inflight.popleft())
