"""Logical-axis -> mesh-axis mapping, plus the DFL fleet-sharding handle.

Every parameter / activation in the model zoo is annotated with *logical* axis
names.  This module turns those names into concrete ``PartitionSpec``s for the
active mesh, dropping any mesh axis that does not evenly divide the tensor
dimension (e.g. smollm's 15 attention heads stay replicated on a 16-way model
axis instead of forcing GSPMD padding).

The mapping is a plain dict, so a caller can override single rules
(``use_sharding_rules(mesh, overrides)``).

``FleetSharding`` is the sharded DFL engines' mesh handle: a hashable wrapper
around the 1-D fleet mesh (``launch.mesh.make_fleet_mesh``) that rides through
``jax.jit`` as a static argument so the hot paths (``dfl.worker.round_step`` /
``mega_round_step``, ``dfl.lm_worker.LMEngine``) can place the sharding
constraints that keep the resident ``(N_pad, P)`` buffers row-partitioned
across rounds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default logical->mesh rules.  Values are tuples of mesh axis names (applied
# jointly to one tensor dim) or None (replicated).
DEFAULT_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    # activations
    "data": ("pod", "data"),        # global batch
    "seq_act": ("data",),           # sequence-parallel activations / caches
    "embed_act": None,              # model-dim of activations: replicated
    "mlp_act": ("model",),
    "vocab_act": ("model",),
    "heads": ("model",),
    "q_seq": None,                  # context-parallel attention (perf override)
    "experts_act": ("model",),
    # params (fsdp over `data`, tensor-parallel over `model`; replicated over
    # `pod` — each pod is a DFL worker holding its own replica)
    "embed": ("data",),
    "mlp": ("model",),
    "vocab": ("model",),
    "kv_heads": ("model",),
    "head_dim": None,
    "experts": ("model",),
    "expert_mlp": ("model",),       # fallback TP inside experts (few-expert MoE)
    "expert_embed": ("data",),      # fsdp axis of expert weights (H2 knob)
    "moe_contract": None,           # dispatch-buffer d axis (H2: ('data',) =>
                                    #   co-sharded contraction, psum instead of
                                    #   weight all-gather)
    "expert_cap": ("model",),       # fallback for the dispatch buffer
    "moe_h_cap": ("model",),        # capacity dim of expert activations (H2:
                                    #   ('data',) turns the contraction psum
                                    #   into a reduce-scatter)
    "ssm_inner": ("model",),
    "ssm_state": None,
    "rnn_width": ("model",),
    "stack": None,                  # stacked-layer leading axis (scan layers)
    "worker": ("data",),            # DFL simulation: stacked worker axis
}


class _Ctx:
    def __init__(self, mesh: Mesh, rules: Dict[str, Optional[Tuple[str, ...]]]):
        self.mesh = mesh
        self.rules = rules


_ACTIVE: Optional[_Ctx] = None


@contextlib.contextmanager
def use_sharding_rules(mesh: Mesh, overrides: Optional[Dict[str, Optional[Tuple[str, ...]]]] = None):
    """Enable `constrain()` + `logical_spec()` for the dynamic extent."""
    global _ACTIVE
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    prev, _ACTIVE = _ACTIVE, _Ctx(mesh, rules)
    try:
        yield
    finally:
        _ACTIVE = prev


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE.mesh if _ACTIVE is not None else None


def abstract_mesh(sizes: Sequence[int], names: Sequence[str]):
    """A device-free ``jax.sharding.AbstractMesh`` for spec resolution
    (``logical_spec`` / ``tree_shardings`` read only its shape and names)."""
    return jax.sharding.AbstractMesh(tuple(sizes), tuple(names))


def _resolve_dim(logical: Optional[str], dim: int, mesh: Mesh,
                 rules: Dict[str, Optional[Tuple[str, ...]]],
                 used: Optional[set] = None):
    """Mesh axes for one tensor dim: skips axes already used by another dim of
    the same tensor and axes that don't divide the dim evenly."""
    if logical is None:
        return None
    axes = rules.get(logical)
    if not axes:
        return None
    used = used if used is not None else set()
    picked = []
    divisor = 1
    for ax in axes:
        if ax not in mesh.shape or ax in used:
            continue
        n = mesh.shape[ax]
        if dim % (divisor * n) == 0:
            picked.append(ax)
            divisor *= n
    if not picked:
        return None
    return tuple(picked) if len(picked) > 1 else picked[0]


def logical_spec(logical_axes: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Optional[Mesh] = None,
                 rules: Optional[Dict[str, Optional[Tuple[str, ...]]]] = None) -> P:
    """PartitionSpec for a tensor with the given logical axes and shape."""
    if mesh is None:
        assert _ACTIVE is not None, "no active sharding context"
        mesh = _ACTIVE.mesh
        rules = rules or _ACTIVE.rules
    rules = rules or DEFAULT_RULES
    # each mesh axis may be assigned to at most one dim of one tensor
    used: set = set()
    entries = []
    for logical, dim in zip(logical_axes, shape):
        r = _resolve_dim(logical, dim, mesh, rules, used)
        if r is None:
            entries.append(None)
            continue
        used.update(r if isinstance(r, tuple) else (r,))
        entries.append(r)
    return P(*entries)


def constrain(x, logical_axes: Sequence[Optional[str]]):
    """`with_sharding_constraint` under the active rules; no-op outside a ctx."""
    if _ACTIVE is None:
        return x
    spec = logical_spec(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_ACTIVE.mesh, spec))


# --------------------------------------------------------------------------- #
# DFL fleet sharding: the resident (N, P) buffers' row partition
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class FleetSharding:
    """Hashable handle for the 1-D fleet mesh the sharded DFL engines run on.

    Frozen + built from hashable jax objects, so it is a valid ``jax.jit``
    static argument: the engine hot paths receive it statically and place
    ``with_sharding_constraint``s, while the host side uses it to pad the
    worker axis to a shard multiple (jax requires evenly divisible
    NamedShardings) and to ``device_put`` operands.  Padding rows are
    permanently idle: never activated, never a mixing row or column, excluded
    from evals — they exist only so GSPMD gets an even row split.
    """
    mesh: Mesh
    axis: str = "fleet"

    @classmethod
    def create(cls, mesh_shards: int) -> "FleetSharding":
        from repro.launch.mesh import FLEET_AXIS, make_fleet_mesh
        return cls(mesh=make_fleet_mesh(mesh_shards), axis=FLEET_AXIS)

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]

    def rows(self) -> NamedSharding:
        """Leading axis split into contiguous per-device blocks."""
        return NamedSharding(self.mesh, P(self.axis))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def for_rows(self, k: int) -> NamedSharding:
        """Row sharding when the leading dim splits evenly, else replicated —
        gathered active-row sets are power-of-two buckets, so they shard
        whenever k >= n_shards; tiny odd sets (and N-clamped buckets that
        lost divisibility) fall back to replication rather than erroring."""
        return self.rows() if k and k % self.n_shards == 0 \
            else self.replicated()

    def pad(self, n: int) -> int:
        """Extra permanently-idle rows needed to make n divisible."""
        return (-n) % self.n_shards

    def put_rows(self, x) -> jax.Array:
        return jax.device_put(x, self.rows())

    def put_rows_padded(self, x) -> jax.Array:
        """Row-shard ``x``, first zero-padding its leading axis to a shard
        multiple — the single definition of the permanently-idle padding
        rows every resident buffer carries under the mesh."""
        extra = self.pad(x.shape[0])
        if extra:
            x = jnp.concatenate(
                [x, jnp.zeros((extra,) + x.shape[1:], x.dtype)])
        return self.put_rows(x)

    def put(self, x) -> jax.Array:
        return jax.device_put(x, self.replicated())


def tree_shardings(logical_tree, shape_tree, mesh: Mesh,
                   rules: Optional[Dict[str, Optional[Tuple[str, ...]]]] = None):
    """Map a pytree of logical-axes tuples + matching ShapeDtypeStructs to
    NamedShardings."""
    rules = rules or DEFAULT_RULES

    def one(logical, sds):
        return NamedSharding(mesh, logical_spec(logical, sds.shape, mesh, rules))

    return jax.tree.map(one, logical_tree, shape_tree,
                        is_leaf=lambda l: isinstance(l, tuple) and all(isinstance(a, (str, type(None))) for a in l))
