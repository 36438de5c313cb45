"""Process-parallel substrate for the hubs-of-hubs federation.

The federation's shards (`repro.serving.federation.InlineShard`) are
analytically-engined, numpy-only event loops, so they parallelize
cleanly across OS processes: this module provides the deterministic
per-shard seed split, the picklable `ShardSpec` a worker needs to build
its shard from scratch, and `ProcessShardHandle` — a pipe-RPC proxy
exposing the exact `InlineShard` surface, so
`repro.serving.federation.FederatedSimulator` drives inline and remote
shards through one interface.

Seed splitting (`shard_seed`) is `jax.random.fold_in`-style: the base
seed and the super-hub id are folded through a specified, platform-stable
mix (`numpy.random.SeedSequence`), so every shard owns an independent RNG
stream derived ONLY from ``(base_seed, super_id)`` — never from
scheduling order.  Since shards share no mutable random state (each
`SimCluster` carries its own generator) a federated run is bit-
deterministic under ANY shard-advance interleave, which is what lets the
process pool below overlap shard execution freely between epochs
(tests/test_federation.py shuffles the advance schedule to prove it).

Placement note: the federation's shard workers are CPU-bound numpy loops,
so `worker_slots` just bounds process fan-out by visible cores.  A chip
belongs to one process, and the parent that spawns the workers has already
imported JAX, so every worker starts with ``JAX_PLATFORMS=cpu`` in its
environment and can never claim the accelerator; `build_federation`
refuses process mode for real engines and device solvers, which would
otherwise run on the workers' CPU.
"""
from __future__ import annotations

import contextlib
import multiprocessing as mp
import os
from dataclasses import dataclass, field

import numpy as np


def shard_seed(base_seed: int, super_id: int) -> int:
    """Fold a super-hub id into the base seed (`fold_in`-style).

    `numpy.random.SeedSequence` entropy mixing is specified and
    platform-stable, so the same ``(base_seed, super_id)`` pair yields
    the same 31-bit seed on every machine — and distinct pairs are
    decorrelated far beyond what ``base_seed + super_id`` would give.
    """
    ss = np.random.SeedSequence((int(base_seed), int(super_id)))
    return int(ss.generate_state(1, np.uint32)[0] % (2**31))


@contextlib.contextmanager
def _cpu_only_children():
    """Hold ``JAX_PLATFORMS=cpu`` in the environment while a worker is
    spawned: the spawned interpreter inherits it, so JAX in the worker never
    opens the accelerator the parent may hold."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


def worker_slots(requested: int | None = None) -> int:
    """Bound process fan-out by visible CPU cores (at least one)."""
    cores = os.cpu_count() or 1
    return max(1, min(requested or cores, cores))


@dataclass
class ShardSpec:
    """Everything a worker process needs to build one federation shard.

    Pure data (profiles are frozen dataclasses of scalars/tuples), so the
    spec pickles across a spawn boundary; the worker materializes the
    `SimCluster`/`IEMASRouter`/`ShardEventLoop` triple itself via
    `repro.serving.federation.InlineShard.from_spec` — the SAME factory
    the inline path uses, which is what keeps process-parallel runs
    bit-identical to inline runs.
    """

    super_id: int
    profiles: list                      # this shard's slice of the fleet
    seed: int                           # shard_seed(base_seed, super_id)
    router_kwargs: dict = field(default_factory=dict)
    loop_kwargs: dict = field(default_factory=dict)
    cluster_kwargs: dict = field(default_factory=dict)


def _shard_worker(conn, spec: ShardSpec) -> None:
    """Worker main: build the shard, then serve pipe-RPC until ``close``.

    Imports the serving stack lazily (inside the process) so the module
    itself stays importable without touching jax; the RPC protocol is
    ``(method_name, args tuple)`` in, ``("ok", result)`` /
    ``("err", repr)`` out.
    """
    try:
        from repro.serving.federation import InlineShard

        shard = InlineShard.from_spec(spec)
        conn.send(("ok", None))
    except Exception as e:          # pragma: no cover - startup failure path
        conn.send(("err", repr(e)))
        return
    while True:
        try:
            msg = conn.recv()
        except EOFError:            # parent died: exit quietly
            return
        if msg is None:
            return
        name, args = msg
        try:
            conn.send(("ok", getattr(shard, name)(*args)))
        except Exception as e:
            conn.send(("err", repr(e)))


class ProcessShardHandle:
    """One federation shard living in its own OS process (pipe-RPC proxy).

    Exposes the `InlineShard` driver surface (``start``, ``inject``,
    ``advance``, ``digest``, ``residuals``, ``extract``, ``admit``,
    ``close_arrivals``, ``finalize``) by forwarding each call over a
    duplex pipe.  Calls are synchronous by default; ``advance`` can be
    split into `advance_async` + `wait` so the parent overlaps all
    shards' epoch work — the actual concurrency win.  Uses the spawn
    start method: the parent has jax initialized, and forking a process
    with live jax threadpools is not safe.  The worker starts with
    ``JAX_PLATFORMS=cpu`` (`_cpu_only_children`).
    """

    def __init__(self, spec: ShardSpec, *, ctx: str = "spawn"):
        self.super_id = spec.super_id
        context = mp.get_context(ctx)
        self._conn, child = context.Pipe()
        self._proc = context.Process(target=_shard_worker,
                                     args=(child, spec), daemon=True)
        with _cpu_only_children():
            self._proc.start()
        child.close()
        self._pending = False
        status, payload = self._conn.recv()     # startup ack
        if status != "ok":
            raise RuntimeError(f"shard {spec.super_id} worker failed to "
                               f"start: {payload}")

    def _call(self, name: str, *args):
        self._conn.send((name, args))
        status, payload = self._conn.recv()
        if status != "ok":
            raise RuntimeError(f"shard {self.super_id}.{name}: {payload}")
        return payload

    def advance_async(self, t_end: float | None) -> None:
        """Kick off one epoch's advance without waiting for the result."""
        self._conn.send(("advance", (t_end,)))
        self._pending = True

    def wait(self):
        """Collect the result of the outstanding `advance_async`."""
        if not self._pending:
            raise RuntimeError("wait() without a pending advance_async()")
        self._pending = False
        status, payload = self._conn.recv()
        if status != "ok":
            raise RuntimeError(f"shard {self.super_id}.advance: {payload}")
        return payload

    def close(self) -> None:
        """Shut the worker down (idempotent)."""
        if self._proc.is_alive():
            try:
                self._conn.send(None)
            except (BrokenPipeError, OSError):
                pass
            self._proc.join(timeout=10)
            if self._proc.is_alive():   # pragma: no cover - hung worker
                self._proc.terminate()
        self._conn.close()

    def __getattr__(self, name):
        # proxy the remaining InlineShard surface verbatim
        if name.startswith("_"):
            raise AttributeError(name)

        def method(*args):
            return self._call(name, *args)

        return method
