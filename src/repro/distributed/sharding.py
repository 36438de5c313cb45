"""Logical-axis sharding rules (t5x/MaxText style) for the whole framework.

Models annotate activations with *logical* axis names via ``shard(x, ...)``
and parameters via per-leaf logical-axes pytrees. A ``ShardingPolicy`` maps
logical names to mesh axes, with automatic divisibility fallback (e.g. 8 KV
heads on a 16-way ``model`` axis fall back to replication), and never assigns
one mesh axis to two dims of the same tensor.

Two rule sets live in one policy:
  * ``acts``   — activation shardings (used by ``shard`` constraints)
  * ``params`` — parameter shardings (FSDP/ZeRO assignments live here)

When no policy is active (unit tests, single-device smoke runs) ``shard`` is
a no-op, so model code runs unchanged on 1 CPU device.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field, replace

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Rules map a logical axis name to a mesh axis name, a tuple of mesh axes,
# or None (replicated). Order matters only through the tensor's own axes.
DEFAULT_RULES: dict = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "qk_dim": None,
    "ff": "model",
    "vocab": "model",
    "expert": "model",
    "kv_lora": None,
    "state": None,
    "inner": "model",   # SSM inner projections (rwkv/mamba d_inner)
    "conv_k": None,
    "lora_rank": None,
    "attn_seq": None,     # q seq in the chunked path (heads carry `model`)
    "attn_kv_seq": None,  # gathered key/value seq
    "attn_head": None,    # head dims in the dense path (seq carries `model`)
    "logit_seq": None,    # LM-head seq dim (vocab carries `model`)
    "cache_seq": None,
    "src_seq": None,
    "patches": None,
    # parameters (stacked layer dim never sharded)
    "layers": None,
    "groups": None,
}

# Training: sequence-parallel residual stream + FSDP parameters over `data`.
TRAIN_RULES = dict(DEFAULT_RULES)
TRAIN_RULES.update({"seq": "model"})
TRAIN_PARAM_RULES = {
    # FSDP: shard the long dim of weight matrices over `data` as well
    "embed": "data",
    "ff": "model",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "expert": "model",
}

# Decode/prefill: weights sharded over model only (no FSDP gather per step);
# batch over data, KV cache heads over model with seq fallback.
DECODE_RULES = dict(DEFAULT_RULES)
DECODE_RULES.update({"seq": None, "cache_seq": None})
DECODE_PARAM_RULES = {
    # ZeRO-style 2D weight sharding for serving: embed dim over `data`,
    # heads/ff/vocab over `model` => 256-way shards; contractions produce
    # small per-token partial-sum all-reduces instead of replicating e.g.
    # mixtral's 282 GB of expert weights per data replica.
    "embed": "data",
    "ff": "model",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "expert": "model",
    "inner": "model",
}


@dataclass(frozen=True)
class ShardingPolicy:
    """Maps logical axis names to mesh axes for activations and params.

    ``acts`` holds the activation rules consulted by `shard`; ``params``
    overlays parameter-specific rules (FSDP/ZeRO assignments) on top of
    them.  Resolution applies divisibility fallback and never assigns one
    mesh axis to two dims of the same tensor."""
    mesh: Mesh
    acts: dict = field(default_factory=lambda: dict(DEFAULT_RULES))
    params: dict = field(default_factory=dict)

    def _axis_size(self, name) -> int:
        return self.mesh.shape.get(name, 1)

    def _resolve(self, logical_axes, dims, rules) -> P:
        """Logical names -> PartitionSpec with divisibility + reuse fallback."""
        used: set = set()
        spec = []
        for i, name in enumerate(logical_axes):
            rule = rules.get(name, None)
            if rule is None:
                spec.append(None)
                continue
            axes = rule if isinstance(rule, tuple) else (rule,)
            # drop axes missing from the mesh or already used by this tensor
            axes = tuple(a for a in axes if a in self.mesh.shape and a not in used)
            if not axes:
                spec.append(None)
                continue
            total = 1
            for a in axes:
                total *= self._axis_size(a)
            if dims is not None and dims[i] % total != 0:
                # divisibility fallback: try shrinking the axis tuple
                while axes and (dims[i] % _prod(self._axis_size(a) for a in axes) != 0):
                    axes = axes[:-1]
                if not axes:
                    spec.append(None)
                    continue
            used.update(axes)
            spec.append(axes if len(axes) > 1 else axes[0])
        while spec and spec[-1] is None:
            spec.pop()
        return P(*spec)

    def act_spec(self, logical_axes, dims=None) -> P:
        """PartitionSpec for an activation under the ``acts`` rules."""
        rules = dict(self.acts)
        return self._resolve(logical_axes, dims, rules)

    def param_spec(self, logical_axes, dims=None) -> P:
        """PartitionSpec for a parameter (``params`` overlaid on ``acts``)."""
        rules = dict(self.acts)
        rules.update(self.params)
        return self._resolve(logical_axes, dims, rules)

    def act_sharding(self, logical_axes, dims=None) -> NamedSharding:
        """`act_spec` bound to this policy's mesh as a NamedSharding."""
        return NamedSharding(self.mesh, self.act_spec(logical_axes, dims))

    def param_sharding(self, logical_axes, dims=None) -> NamedSharding:
        """`param_spec` bound to this policy's mesh as a NamedSharding."""
        return NamedSharding(self.mesh, self.param_spec(logical_axes, dims))

    def with_rules(self, acts=None, params=None) -> "ShardingPolicy":
        """A copy of this policy with rule overrides merged in."""
        new_acts = dict(self.acts)
        new_acts.update(acts or {})
        new_params = dict(self.params)
        new_params.update(params or {})
        return replace(self, acts=new_acts, params=new_params)


def _prod(it):
    out = 1
    for x in it:
        out *= x
    return out


_state = threading.local()


def current_policy() -> ShardingPolicy | None:
    """The thread-local active policy (None outside `apply_policy`)."""
    return getattr(_state, "policy", None)


@contextlib.contextmanager
def apply_policy(policy: ShardingPolicy | None):
    """Make ``policy`` the thread-local active policy for the block."""
    prev = current_policy()
    _state.policy = policy
    try:
        yield policy
    finally:
        _state.policy = prev


def shard(x, *logical_axes):
    """Annotate an activation with logical axes; no-op without a policy."""
    policy = current_policy()
    if policy is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(
            f"shard(): {len(logical_axes)} logical axes for rank-{x.ndim} tensor"
        )
    spec = policy.act_spec(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(policy.mesh, spec))


def param_shardings(policy: ShardingPolicy, abstract_params, param_axes):
    """Pytree of NamedShardings for a params pytree given its logical axes.

    ``param_axes`` mirrors the params pytree with space-separated logical-axis
    strings as leaves, e.g. ``"layers embed ff"``.
    """
    def one(leaf, axes_str):
        axes = tuple(a if a != "." else None for a in axes_str.split())
        if len(axes) != len(leaf.shape):
            raise ValueError(f"axes {axes_str!r} vs shape {leaf.shape}")
        return policy.param_sharding(axes, leaf.shape)

    return jax.tree.map(one, abstract_params, param_axes)
