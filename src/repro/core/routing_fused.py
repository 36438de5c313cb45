"""Fused device-resident routing step: Phase 1 descend -> column-market bids.

The staged router (`core/mechanism.py`) runs the per-batch hot path as a
chain of separately-jitted programs stitched together with NumPy host
round-trips: `_phase1` builds the Eq.-5 feature tensor on host, the affinity
kernel materializes padded ledger tiles per batch, and `dense_jax` bounces
prices through ``np.asarray`` between ε-stages.  This module fuses the whole
step into ONE jitted program that stays device-resident from the ledger
gather to the final auction state:

    (a) Eq.-4 cache affinity — ledger rows gathered from a persistent device
        mirror of the `PaddedLedgerStore` arena (dirty-row scatter updates,
        no per-batch upload), LCP via the cumulative-product-of-equality
        trick, LRU keep-masking and `parent_credit` folded in as a
        scatter-max over parent-candidate rows;
    (b) the Eq.-5 feature tensor assembled from device telemetry vectors;
    (c) Phase-1 QoS prediction — the stacked Hoeffding forests (device
        mirrors refreshed only when tree versions move) descended by the
        same fori_loop walker as `hoeffding._jax_descend`, blended with the
        structural cold-start prior exactly like
        `predictor._blend_with_prior`, then Eq.-1 client values;
    (d) the capacitated-column ε-scaling auction — `dense_jax`'s staged
        ``solve`` composed INSIDE the program (warm attempt under the warm
        round budget with an in-program `lax.cond` cold fallback), with the
        ε schedule (`jax_eps_final` / `warm_eps0`) computed as traced
        scalars instead of host floats.

No host sync happens until the program returns: the single ``np.asarray``
materialization block at the end feeds the same `materialize_staged` /
`package_dense` host packaging (float64 Clarke payments) the staged path
uses, so `IEMASRouter.route_batch` splices fused results identically.

Shape discipline (retrace bound): batch, fleet, token width, parent
candidates, node pools, loop depth and unit count are all padded to pow-2
buckets (`core/buckets.pow2_bucket`), so a serving run traces O(log) fused
programs, not one per batch shape — mirrored by the regression test in
tests/test_routing_fused.py.  The warm-start price grid is the only donated
buffer (it is consumed by the solve and rebuilt from the price book each
round); the ledger arena and forest mirrors persist across calls and are
never donated.  Donation is skipped on CPU where XLA cannot honor it.

Precision contract: the program runs in float32 (default JAX config), while
the staged oracle's Phase 1 is float64 NumPy — assignments agree except
when two assignments' TOTAL welfare lands within the auction's own
ε-optimality gap (measured ~1e-6 relative when it happens), where the
differing float32 welfare bits can legally terminate the ε-scaling on the
other equally-good assignment; payments/estimates agree to ~1e-6 relative
whenever the assignment matches.  A feature landing
within float32 rounding of a trained tree threshold can flip a leaf (same
caveat as `hoeffding._jax_descend`).  The staged path remains the oracle;
parity is property-tested in tests/test_routing_fused.py.
"""
from __future__ import annotations

import numpy as np

from repro.core.affinity import PAD_PROMPT
from repro.core.buckets import pow2_bucket
from repro.core.predictor import N_FEATURES
from repro.core.solvers.dense_common import (THETA, check_start_prices,
                                             column_counts, empty_result,
                                             materialize_staged,
                                             package_dense, warm_round_budget)
from repro.core.solvers.dense_np import _price_grid
from repro.utils.timing import phase_scope

#: solver backends whose bidding loop can compose inside the fused program
#: (both ride `dense_jax._build_jax_solver`; pallas swaps the bid round).
FUSED_SOLVERS = ("dense-jax", "pallas")

_EPS32 = float(np.finfo(np.float32).eps)

_SCATTER = None


def _donate_ok() -> bool:
    """Whether buffer donation is honored on this backend (not on CPU)."""
    import jax
    return jax.default_backend() != "cpu"


def _scatter_fn():
    """Jitted dirty-row scatter into the device ledger mirror (donated)."""
    global _SCATTER
    if _SCATTER is None:
        import jax

        def scat(tokens, lens, rows, vals, lvals):
            return tokens.at[rows].set(vals), lens.at[rows].set(lvals)

        _SCATTER = jax.jit(scat,
                           donate_argnums=(0, 1) if _donate_ok() else ())
    return _SCATTER


class _LedgerMirror:
    """Device-resident copy of the `PaddedLedgerStore` token arena.

    ``sync`` drains the store's dirty-row set and scatters just those rows
    into the persistent device arrays (pow-2 bucketed row count per scatter,
    so the scatter program itself stays retrace-bounded); a ``shape_version``
    bump (arena regrow) triggers a full re-upload instead.  Rows beyond the
    dirty count pad with row 0 — the store's reserved all-pad sentinel —
    whose rewrite is a no-op by construction.
    """

    def __init__(self, store):
        self.store = store
        self.tokens = None
        self.lens = None
        self._shape_version = -1

    def sync(self):
        """Bring the device arena up to date with the host store."""
        import jax.numpy as jnp

        st = self.store
        if self.tokens is None or self._shape_version != st.shape_version:
            st.consume_dirty()          # the full upload covers everything
            self.tokens = jnp.asarray(st.tokens)
            self.lens = jnp.asarray(st.lens)
            self._shape_version = st.shape_version
            return
        rows = st.consume_dirty()
        if rows.size == 0:
            return
        rb = pow2_bucket(rows.size)
        rpad = np.zeros(rb, np.int32)   # pad with the row-0 sentinel
        rpad[: rows.size] = rows
        self.tokens, self.lens = _scatter_fn()(
            self.tokens, self.lens, rpad, st.tokens[rpad], st.lens[rpad])


class _ForestMirror:
    """Device copy of one target's stacked Hoeffding forest.

    Piggybacks on `PredictorPool._stacked_forest` (host incremental restack)
    and re-uploads at two speeds, mirroring the host cache: a structure
    change (split / membership, detected by node count or agent-id key)
    re-uploads all five node arrays padded to the pow-2 node bucket; mere
    leaf-value drift (tree version counters moved, node count unchanged)
    re-uploads only the value array.
    """

    def __init__(self):
        self._key = None
        self._versions = None
        self.arrays = None              # (feature, threshold, left, right, value, roots)
        self.depth_bucket = 4

    def sync(self, pool, name: str, agent_ids: list, mb: int):
        """Refresh the device forest; returns (arrays, static depth bucket)."""
        import jax.numpy as jnp

        stacked, roots = pool._stacked_forest(name, agent_ids)
        versions = tuple(getattr(pool._preds[a], name)._version
                         for a in agent_ids)
        n_nodes = len(stacked.feature)
        kb = pow2_bucket(n_nodes)
        key = (tuple(agent_ids), n_nodes, mb)
        if key != self._key:
            feat = np.full(kb, -1, np.int32)        # padded nodes are leaves
            feat[:n_nodes] = stacked.feature
            thr = np.zeros(kb, np.float32)
            thr[:n_nodes] = stacked.threshold
            left = np.zeros(kb, np.int32)
            left[:n_nodes] = stacked.left
            right = np.zeros(kb, np.int32)
            right[:n_nodes] = stacked.right
            val = np.zeros(kb, np.float32)
            val[:n_nodes] = stacked.value
            rootpad = np.zeros(mb, np.int32)        # padded agents: tree 0
            rootpad[: len(roots)] = roots
            self.arrays = tuple(jnp.asarray(a) for a in
                                (feat, thr, left, right, val, rootpad))
            self._key = key
            self._versions = versions
        elif versions != self._versions:
            val = np.zeros(kb, np.float32)
            val[:n_nodes] = stacked.value
            self.arrays = self.arrays[:4] + (jnp.asarray(val),
                                             self.arrays[5])
            self._versions = versions
        self.depth_bucket = pow2_bucket(stacked.depth + 1, floor=4)
        return self.arrays, self.depth_bucket


def _build_program(warm: bool, has_parents: bool, budget: int,
                   max_rounds: int, bid_round):
    """Trace-time factory for one fused program variant.

    ``warm``/``has_parents`` select program structure (warm solve + cold
    fallback vs cold only; parent-credit scatter present or compiled out);
    ``budget`` is the warm attempt's static round cap.  Everything else is
    shape-polymorphic under jit.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core.solvers.dense_jax import _build_jax_solver

    solve_cold = _build_jax_solver(max_rounds, bid_round)
    solve_warm = _build_jax_solver(budget, bid_round) if warm else None

    def program(arena, alen, lrows, pmat, plen, keep, crows, cj, ckeep,
                turns, dom, req_mask, router_scalars, a_inflight, a_rps,
                caps_f, ext, agent_mask, blend, f_lat, f_cst, f_q,
                val_cfg, counts, p0, *, dl, dc, dq):
        fdt = p0.dtype
        nb, mb = dom.shape

        # the three stages carry stable names in the compiled program
        # (op metadata), so a device trace can attribute its operations
        with jax.named_scope("iemas.fused/affinity"):
            # ---- (a) Eq.-4 affinity: arena gather + cumprod-of-equality LCP
            def lcp_scores(rows_ix, prompts, plens):
                led = arena[rows_ix]                       # (B, mb, L)
                llen = alen[rows_ix]
                eq = (prompts[:, None, :] == led).astype(jnp.int32)
                raw = jnp.cumprod(eq, axis=-1).sum(-1)
                lcp = jnp.minimum(raw, jnp.minimum(plens[:, None], llen))
                pl1 = jnp.maximum(plens[:, None], 1).astype(fdt)
                sc = lcp.astype(fdt) / pl1
                # recurrent agents: exact-extension-only cache reuse
                full_prev = (lcp == llen) & (llen > 0)
                return jnp.where(ext[None, :],
                                 jnp.where(full_prev, llen.astype(fdt) / pl1,
                                           0.0), sc)

            o = jnp.where(keep, lcp_scores(lrows, pmat, plen), 0.0)
            if has_parents:
                # precedence credit: candidate (row, parent-session) pairs were
                # flattened on host; fold their best affinity into o by a
                # scatter-max (cj == nb marks padding, dropped by mode="drop")
                cjc = jnp.clip(cj, 0, nb - 1)
                cred = jnp.where(ckeep,
                                 lcp_scores(crows, pmat[cjc], plen[cjc]), 0.0)
                o = o.at[cj].max(cred, mode="drop")

        with jax.named_scope("iemas.fused/predict"):
            # ---- (b) Eq.-5 feature tensor, assembled on device
            util = a_inflight / jnp.maximum(1.0, caps_f)

            def bc(v):
                return jnp.broadcast_to(v, (nb, mb))

            X = jnp.stack([
                bc(plen.astype(fdt)[:, None]), bc(turns[:, None]), o,
                bc(router_scalars[0]), bc(router_scalars[1]),
                bc(a_inflight[None, :]), bc(a_rps[None, :]),
                bc(caps_f[None, :]), bc(util[None, :]), dom,
            ], axis=-1)

            # ---- (c) Phase-1 descend over the stacked forests + prior blend
            flat = X.reshape(nb * mb, N_FEATURES)
            rows = jnp.arange(nb * mb)
            col = jnp.arange(nb * mb, dtype=jnp.int32) % mb

            def desc(forest, depth):
                feature, threshold, left, right, value, roots = forest

                def body(_, cur):
                    f = feature[cur]
                    internal = f >= 0
                    go_left = flat[rows, jnp.where(internal, f, 0)] \
                        <= threshold[cur]
                    nxt = jnp.where(go_left, left[cur], right[cur])
                    return jnp.where(internal, nxt, cur)

                return value[lax.fori_loop(0, depth, body,
                                           roots[col])].reshape(nb, mb)

            raw_lat = desc(f_lat, dl)
            raw_cst = desc(f_cst, dc)
            raw_q = desc(f_q, dq)

            # transcription of predictor._blend_with_prior (same op order)
            (lpt, lb_, miss, hit, out_, ewma, n_obs, warm_n, prior_q, rep,
             expl) = blend
            pl_, aff, util2 = X[..., 0], X[..., 2], X[..., 8]
            uncached = pl_ * (1.0 - aff)
            prior_lat = (lb_ + lpt * uncached) * (1.0 + util2)
            npmt = jnp.trunc(pl_)
            nhit = aff * npmt
            prior_cst = miss * (npmt - nhit) + hit * nhit + out_ * ewma
            wgt = jnp.minimum(1.0, n_obs / 60.0) * rep
            lat = (1.0 - wgt) * prior_lat + wgt * jnp.maximum(0.0, raw_lat)
            cst = (1.0 - wgt) * prior_cst + wgt * jnp.maximum(0.0, raw_cst)
            cold = n_obs < warm_n
            lat = jnp.where(cold, prior_lat, lat)
            cst = jnp.where(cold, prior_cst, cst)
            qual = jnp.where(cold, prior_q * rep,
                             jnp.clip(raw_q, 0.0, 1.0) * rep)
            # optimism bonus (predictor._optimism): applied only where the
            # per-agent explore knob is nonzero, so the default-0 fleet keeps
            # the exact pre-bonus values (no min-clamp is ever taken)
            qual = jnp.where(expl != 0.0,
                             jnp.minimum(1.0, qual
                                         + expl / jnp.sqrt(1.0 + n_obs)),
                             qual)

            # ---- Eq.-1 client value -> pruned welfare
            #      (valuation.client_value)
            delta, lscale, vscale = val_cfg[0], val_cfg[1], val_cfg[2]
            values = vscale * (delta * jnp.clip(qual, 0.0, 1.0)
                               - (1.0 - delta) * lat / lscale)
            W = values - cst
            W = jnp.where(W > 0.0, W, 0.0)
            W = jnp.where(req_mask[:, None] & agent_mask[None, :], W, 0.0)

        with jax.named_scope("iemas.fused/auction"):
            # ---- ε schedule as traced scalars (dense_common.jax_eps_final /
            #      warm_eps0; the staged path computes these on host floats)
            wmax = jnp.max(jnp.where(counts[None, :] > 0, W, 0.0))
            anchor = jnp.maximum(wmax, 1.0)
            eps_final = jnp.maximum(1e-5 * anchor, 64.0 * _EPS32 * anchor)
            theta = jnp.asarray(THETA, fdt)
            cold_eps0 = jnp.maximum(wmax / theta, eps_final)

            # ---- (d) capacitated-column ε-scaling auction, in-program
            if warm:
                # fine schedule iff the seed carries price mass above it
                # (warm_eps0); fine <= cold_eps0 by construction, so the host
                # path's min() is already folded in
                fine = jnp.maximum(wmax / theta ** 3, eps_final)
                eps0 = jnp.where(p0.max() > fine, fine, cold_eps0)
                up, ao, uo, rounds = solve_warm(W, counts, p0, eps0, eps_final,
                                                theta)
                tripped = rounds >= budget

                def cold_solve(_):
                    return solve_cold(W, counts, jnp.zeros_like(p0), cold_eps0,
                                      eps_final, theta)

                def keep(_):
                    return up, ao, uo, rounds

                up, ao, uo, rounds = lax.cond(tripped, cold_solve, keep,
                                              operand=None)
            else:
                up, ao, uo, rounds = solve_cold(W, counts, p0, cold_eps0,
                                                eps_final, theta)
                tripped = jnp.asarray(False)
        return (lat, cst, qual, values, X, up, ao, uo, rounds, tripped,
                eps_final, wmax)

    donate = ("p0",) if _donate_ok() else ()
    return jax.jit(program, static_argnames=("dl", "dc", "dq"),
                   donate_argnames=donate)


class FusedRoutingStep:
    """One device-resident program per route_batch call (see module doc).

    Owned by an `IEMASRouter` constructed with ``fused=True`` (which
    validates ``n_hubs == 1`` and a `FUSED_SOLVERS` backend).  ``step``
    replaces the staged ``_phase1`` + ``run_sharded_auction`` pair for the
    single global market; spill, price-book splice and Phase-3 payments
    remain on the shared host path so fused and staged results package
    identically.
    """

    def __init__(self, router, max_rounds: int = 200_000):
        self.router = router
        if router.solver == "pallas":
            from repro.core.solvers.pallas_backend import _bid_round_pallas
            self.bid_round = _bid_round_pallas
        else:
            self.bid_round = None
        self.max_rounds = max_rounds
        self.ledger_mirror = _LedgerMirror(router.ledger.store)
        self.forests = {name: _ForestMirror()
                        for name in ("lat", "cost", "quality")}
        self._programs: dict = {}
        self._cache_seen = 0

    def cache_size(self) -> int:
        """Total traced-program count across the fused program variants —
        the retrace-bound regression signal (pow-2 bucketing keeps it
        O(log) in batch/fleet/ledger growth)."""
        return sum(p._cache_size() for p in self._programs.values())

    def _program(self, warm: bool, has_parents: bool, budget: int):
        key = (warm, has_parents, budget)
        prog = self._programs.get(key)
        if prog is None:
            prog = _build_program(warm, has_parents, budget, self.max_rounds,
                                  self.bid_round)
            self._programs[key] = prog
        return prog

    def step(self, requests, live, telemetry, caps,
             start_prices=None):
        """Run the fused program for one batch.

        ``requests``/``live``/``telemetry``/``caps`` exactly as
        `IEMASRouter.route_batch` prepares them; ``start_prices`` is the
        hub-0 flat warm-start seed (or None).  Returns ``(lat, cst, qual,
        values, X, result)`` — float64 host matrices shaped like the staged
        `_phase1` outputs plus the packaged
        :class:`~repro.core.solvers.base.AuctionResult`.

        Three profiler spans split the call: ``fused.assemble`` (host
        inputs up to the program call), ``fused.device`` (the program and
        the one materialization of its outputs; counters ``rounds``,
        ``warm``, ``fallback``, ``retraces``) and ``fused.settle`` (float64
        Clarke payments and packaging).
        """
        prof = getattr(self.router, "profiler", None)
        n, m = len(requests), len(live)
        with phase_scope(prof, "fused.assemble"):
            prog, args, static, counts_np, warm = self._assemble(
                requests, live, telemetry, caps, start_prices)
        with phase_scope(prof, "fused.device") as span:
            out = self._materialize(prog(*args, **static), n, m,
                                    int(counts_np.max()) if m else 0)
            c = self.cache_size()
            span.set(rounds=out["rounds"], warm=warm,
                     fallback=warm and out["tripped"],
                     retraces=max(0, c - self._cache_seen))
            self._cache_seen = c
        with phase_scope(prof, "fused.settle"):
            result = self._settle(out, caps, counts_np, warm, n)
        return (out["lat"], out["cst"], out["qual"], out["values"], out["X"],
                result)

    def _assemble(self, requests, live, telemetry, caps, start_prices):
        """Host-side inputs of one fused call: mirror syncs, request
        packing, the blend table and the warm-start price grid.  Returns
        ``(program, args, static args, column counts, warm)``."""
        r = self.router
        n, m = len(requests), len(live)
        nb, mb = pow2_bucket(n), pow2_bucket(m)
        agent_ids = [a.agent_id for a in live]
        sess = [req.meta.get("session", req.dialogue_id) for req in requests]
        ledger = r.ledger
        store = ledger.store

        # ---- tiny index/param arrays only (the token payloads and every
        #      O(n*m) operation stay on device)
        self.ledger_mirror.sync()
        L = store.width
        lrows = np.zeros((nb, mb), np.int32)
        lrows[:n, :m] = store.rows_for(sess, agent_ids)
        pmat = np.full((nb, L), PAD_PROMPT, np.int32)
        plen = np.zeros(nb, np.int32)
        for j, req in enumerate(requests):
            t = np.asarray(req.tokens, np.int32)
            k = min(len(t), L)          # LCP is clamped by entry length <= L
            pmat[j, :k] = t[:k]
            plen[j] = len(t)
        slots = [a.cache_slots for a in live]
        keep = np.zeros((nb, mb), bool)
        keep[:n, :m] = ledger.keep_mask(sess, agent_ids, slots)

        parents = [req.meta.get("parent_sessions", ()) for req in requests]
        cand = [(j, s) for j, ps in enumerate(parents) for s in ps]
        has_parents = bool(cand)
        cb = pow2_bucket(len(cand)) if has_parents else 8
        crows = np.zeros((cb, mb), np.int32)
        cj = np.full(cb, nb, np.int32)          # nb = scatter-drop sentinel
        ckeep = np.zeros((cb, mb), bool)
        if has_parents:
            csess = [s for _, s in cand]
            crows[: len(cand), :m] = store.rows_for(csess, agent_ids)
            cj[: len(cand)] = [j for j, _ in cand]
            ck = np.ones((len(cand), m), bool)
            for i, (aid, sl) in enumerate(zip(agent_ids, slots)):
                if sl > 0:
                    recent = ledger.recent_sessions(aid, int(sl))
                    ck[:, i] = [s in recent for s in csess]
            ckeep[: len(cand), :m] = ck

        inflight = telemetry.get("agent_inflight", {})
        agent_rps = telemetry.get("agent_rps", {})
        turns = np.zeros(nb, np.float32)
        turns[:n] = [float(req.turn) for req in requests]
        dom = np.zeros((nb, mb), np.float32)
        dom_rows: dict[str, np.ndarray] = {}
        for j, req in enumerate(requests):
            row = dom_rows.get(req.domain)
            if row is None:
                row = dom_rows[req.domain] = np.array(
                    [float(req.domain in a.domains) for a in live],
                    np.float32)
            dom[j, :m] = row
        req_mask = np.zeros(nb, bool)
        req_mask[:n] = True
        agent_mask = np.zeros(mb, bool)
        agent_mask[:m] = True
        a_inflight = np.zeros(mb, np.float32)
        a_rps = np.zeros(mb, np.float32)
        caps_f = np.zeros(mb, np.float32)
        ext = np.zeros(mb, bool)
        for i, a in enumerate(live):
            a_inflight[i] = float(inflight.get(a.agent_id, 0))
            a_rps[i] = float(agent_rps.get(a.agent_id, 0.0))
            caps_f[i] = float(a.capacity)
            ext[i] = a.recurrent
        router_scalars = np.array(
            [float(telemetry.get("router_inflight", 0)),
             float(telemetry.get("router_rps", 0.0))], np.float32)

        # per-agent blend parameters (padded agents: all-zero params with
        # warm_n=1 -> cold prior-only -> value 0, masked out regardless)
        blend = np.zeros((11, mb), np.float32)
        for i, aid in enumerate(agent_ids):
            p = r.pool[aid]
            blend[:, i] = (p.prior_lpt, p.prior_lb, p.prices.miss,
                           p.prices.hit, p.prices.out, p.ewma_gen,
                           p.n_obs, p.warm_n, p.prior_q, p.reputation,
                           p.explore)
        blend[7, m:] = 1.0

        f_lat, dl = self.forests["lat"].sync(r.pool, "lat", agent_ids, mb)
        f_cst, dc = self.forests["cost"].sync(r.pool, "cost", agent_ids, mb)
        f_q, dq = self.forests["quality"].sync(r.pool, "quality",
                                               agent_ids, mb)

        vc = r.valuation
        val_cfg = np.array([vc.delta, vc.latency_scale, vc.value_scale],
                           np.float32)

        counts_np = column_counts(caps, n)
        K = int(counts_np.sum())
        cmax = int(counts_np.max()) if m else 0
        cbu = pow2_bucket(max(cmax, 1))
        counts = np.zeros(mb, np.int32)
        counts[:m] = counts_np
        warm = start_prices is not None and K > 0
        grid = np.zeros((mb, cbu), np.float32)
        if warm:
            p0 = check_start_prices(start_prices, K)
            grid[:m, :cmax] = _price_grid(p0, counts_np, cmax)
        budget = warm_round_budget(nb, mb * cbu, self.max_rounds) \
            if warm else 0

        prog = self._program(warm, has_parents, budget)
        args = (self.ledger_mirror.tokens, self.ledger_mirror.lens, lrows,
                pmat, plen, keep, crows, cj, ckeep, turns, dom, req_mask,
                router_scalars, a_inflight, a_rps, caps_f, ext, agent_mask,
                blend, f_lat, f_cst, f_q, val_cfg, counts, grid)
        return prog, args, dict(dl=dl, dc=dc, dq=dq), counts_np, warm

    @staticmethod
    def _materialize(out, n: int, m: int, cmax: int) -> dict:
        """The batch's ONE device->host boundary: every output the
        RouteDecisions need, after the auction settled, cut to the
        batch's own n x m market."""
        (lat, cst, qual, values, X, up, ao, uo, rounds, tripped, eps_f,
         wmax) = out
        return {
            "lat": np.asarray(lat, np.float64)[:n, :m],
            "cst": np.asarray(cst, np.float64)[:n, :m],
            "qual": np.asarray(qual, np.float64)[:n, :m],
            "values": np.asarray(values, np.float64)[:n, :m],
            "X": np.asarray(X, np.float64)[:n, :m],
            "up": np.asarray(up, np.float64)[:m, :cmax],
            "ao": np.asarray(ao)[:n], "uo": np.asarray(uo)[:n],
            "rounds": int(rounds), "tripped": bool(tripped),
            "eps_final": float(eps_f), "wmax": float(wmax)}

    def _settle(self, out: dict, caps, counts_np, warm: bool, n: int):
        """Host packaging — same helpers as the staged backends, float64
        weights recomputed host-side for Clarke payments
        (auction._prune)."""
        r = self.router
        cst = out["cst"]
        w64 = out["values"] - cst
        w64 = np.where(w64 > 0.0, w64, 0.0)
        if n == 0 or int(counts_np.sum()) == 0 or out["wmax"] <= 0.0:
            dres = empty_result(n, counts_np)
        else:
            if out["rounds"] >= self.max_rounds:
                raise RuntimeError(
                    f"dense auction (fused/{r.solver}) failed to converge "
                    f"in {self.max_rounds} rounds (n={n}, m={len(caps)})")
            dres = materialize_staged(
                w64, counts_np, out["up"], out["ao"], out["uo"],
                out["rounds"], out["eps_final"], warm_started=warm,
                fallback=warm and out["tripped"])
        return package_dense(r.solver, w64, cst, caps, dres)
