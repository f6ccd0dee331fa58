"""One frozen, hashable config for the whole kernel plane.

``KernelConfig`` holds the kernel backend, the interpret policy and the
per-kernel block sizes.  It is a frozen dataclass of hashable scalars, so
ONE object rides through ``jax.jit`` static arguments, engine cache keys,
and ``ModelConfig`` (the zoo forward passes read ``cfg.kernels``) on both
DFL planes plus serving.

Pure stdlib + jax import — safe to import from ``configs.base`` without
cycles (nothing here touches models, engines, or the kernel modules).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

BACKENDS = ("reference", "pallas")


def resolve_interpret(interpret: Union[str, bool]) -> bool:
    """``"auto"`` -> interpret everywhere except a real TPU backend (the CI
    oracle contract: CPU runs the kernels through the Pallas interpreter,
    TPU compiles them via Mosaic); explicit booleans pass through."""
    if interpret == "auto":
        import jax
        return jax.default_backend() != "tpu"
    return bool(interpret)


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Kernel-plane surface: which lowering, how it executes, how it tiles.

    ``backend``
        ``"reference"`` (default) — pure jnp/einsum lowerings everywhere;
        the tier-1 CI oracle.  ``"pallas"`` — route Eq. 4 mixing through the
        panel ``aggregate*`` kernels, sim-plane local SGD through the
        VMEM-resident fused-SGD kernel, and the zoo forward passes through
        ``flash_attention``/``ssd_chunk``/``moe_router``.
    ``interpret``
        ``"auto"`` (default) — Pallas interpret mode off-TPU, compiled
        Mosaic on TPU.  ``True`` forces the interpreter (debugging on TPU);
        ``False`` forces compilation (TPU only — rejected with an actionable
        message by the engine configs when the backend cannot compile).
    block sizes
        Per-op tile shapes, validated against TPU tiling at construction:
        ``agg_p_blk`` — the (·, p_blk) parameter-axis panel of the aggregate
        kernels, ``None`` (default) = derived from the operand shapes and a
        VMEM budget (``kernels.aggregate.panel_width``), an int pins it;
        ``attn_blk_q``/``attn_blk_k`` — flash-attention query/key tiles;
        ``moe_blk_t`` — router token-panel rows.
    """
    backend: str = "reference"
    interpret: Union[str, bool] = "auto"
    agg_p_blk: Optional[int] = None
    attn_blk_q: int = 128
    attn_blk_k: int = 128
    moe_blk_t: int = 256

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"KernelConfig.backend={self.backend!r}: expected one of "
                f"{BACKENDS} — 'reference' is the jnp oracle, 'pallas' the "
                f"kernel plane (interpret mode on CPU, Mosaic on TPU)")
        if not (self.interpret == "auto" or self.interpret is True
                or self.interpret is False):
            raise ValueError(
                f"KernelConfig.interpret={self.interpret!r}: expected "
                f"'auto', True, or False ('auto' = interpret everywhere "
                f"except a real TPU backend)")
        for name, mult, what in (("agg_p_blk", 128, "lane"),
                                 ("attn_blk_q", 8, "sublane"),
                                 ("attn_blk_k", 128, "lane"),
                                 ("moe_blk_t", 8, "sublane")):
            v = getattr(self, name)
            if name == "agg_p_blk" and v is None:
                continue
            if not (isinstance(v, int) and not isinstance(v, bool)
                    and v > 0 and v % mult == 0):
                raise ValueError(
                    f"KernelConfig.{name}={v!r}: must be a positive "
                    f"multiple of {mult} (TPU {what} tiling — see "
                    f"docs/ARCHITECTURE.md, kernel plane)")

    @property
    def use_pallas(self) -> bool:
        return self.backend == "pallas"

    def resolve_interpret(self) -> bool:
        """The concrete interpret flag for this process' jax backend."""
        return resolve_interpret(self.interpret)

    def check_executable(self, where: str) -> None:
        """Actionable rejection for combinations that cannot run here:
        ``interpret=False`` pins the compiled Mosaic lowering, which only a
        TPU backend can execute.  Called from the engine config
        ``__post_init__``s so a bad run dies at construction, not mid-jit."""
        import jax
        if (self.use_pallas and self.interpret is False
                and jax.default_backend() != "tpu"):
            raise ValueError(
                f"{where}: KernelConfig(interpret=False) forces the "
                f"compiled Mosaic lowering, but the active jax backend is "
                f"{jax.default_backend()!r} — use interpret='auto' "
                f"(interpret off-TPU, compiled on TPU) or True")
