"""Pallas kernels for the framework's compute hot-spots.

Two-file pattern per op: ``<name>.py`` holds the `pl.pallas_call` kernel,
which callers import (compiled on TPU, interpret mode elsewhere: see
`resolve_interpret`), and ``ref.py`` the simplest-possible pure-jnp oracle
it is validated against.  Current kernels: LCP affinity (router Phase 1), the dense
auction's forward-bidding round (router Phase 2), the MoE's grouped expert
FFN (serving engines), flash/decode attention, WKV6 and SSD recurrences.
"""


def resolve_interpret(interpret: bool | None) -> bool:
    """Every kernel's ``interpret`` convention: an explicit bool is kept;
    None means compiled Pallas on a TPU backend and interpret mode on any
    other backend."""
    if interpret is None:
        import jax

        return jax.default_backend() != "tpu"
    return interpret
