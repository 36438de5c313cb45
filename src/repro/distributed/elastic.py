"""Elastic scaling: re-mesh + reshard state when the device pool changes.

Checkpoints store full (unsharded) arrays, so elasticity is: rebuild the
mesh at the new size, re-derive shardings from the same logical-axis rules
(divisibility fallback handles non-power-of-two survivors), and device_put
the restored state. Serving-side elasticity (agents joining/leaving the
market) lives in core.mechanism.add_agent/remove_agent, which stamp every
membership change with an :class:`AgentSetVersion` — the version gates
cross-round warm-start state (hub slot prices) so nothing learned about one
agent set is replayed against another.

jax is imported lazily: the membership-versioning side of this module is
consumed by the (numpy-only) routing core.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class AgentSetVersion:
    """Monotonic stamp for the serving market's agent membership.

    The router bumps it on every agent join/leave/hub-rebuild; consumers of
    per-agent-set caches (e.g. `repro.core.hub.SlotPriceBook`) key their
    entries by the version at store time and treat any mismatch as a cold
    start.  ``fingerprint`` additionally binds an exact agent-id tuple, for
    caches that must also invalidate on *subset* changes (quarantine flips
    the live set without changing membership, so a version alone is not
    enough).
    """

    version: int = 0

    def bump(self) -> int:
        """Advance to (and return) the next version."""
        self.version += 1
        return self.version

    def fingerprint(self, agent_ids) -> tuple[int, tuple[str, ...]]:
        """(version, exact id tuple) — the full warm-start cache key."""
        return self.version, tuple(agent_ids)


def remesh(n_devices: int, *, data_model_ratio: float = 1.0,
           devices=None):
    """Largest (data, model) mesh fitting n_devices, preferring square-ish
    factorizations scaled by ``data_model_ratio`` (= data/model)."""
    import jax

    devices = list(devices or jax.devices())[:n_devices]
    n = len(devices)
    best = (1, n)
    best_score = -1.0
    for d in range(1, n + 1):
        if n % d:
            continue
        m = n // d
        ratio = d / m
        score = -abs(np.log(ratio / data_model_ratio))
        if score > best_score:
            best, best_score = (d, m), score
    d, m = best
    return jax.make_mesh((d, m), ("data", "model"), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def reshard_state(state, param_axes, mesh, rules_acts: dict,
                  rules_params: dict):
    """device_put a restored pytree onto a new mesh using logical rules."""
    import jax

    from repro.distributed.sharding import ShardingPolicy, param_shardings

    policy = ShardingPolicy(mesh, acts=rules_acts, params=rules_params)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), state)
    shardings = param_shardings(policy, abstract, param_axes)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), state, shardings)
