"""JAX-native sweep executor: jit/`lax.while_loop` steppers behind the
`(B, ...)` seam.

`repro.core.engine.vectorized` documents its batch layout as "the seam a
future `jax.vmap`/Pallas stepper plugs into"; this module is that
stepper. The host-side orchestration (planning, BMF monitor-and-replan,
result bookkeeping) stays in `vectorized.py` — this module replaces only
the *event loops* with jit-compiled device programs:

* `JaxRoundEngine.execute_round` — the masked round stepper
  (`execute_round_batch`'s twin) as one `lax.while_loop` over static
  padded `(B, T, H)` shapes: per-case dt / epoch / completion masks,
  fan-in segment reductions re-expressed as dense `(B, T, N)`
  one-hot matches (cumsum positions, max-reductions), all in float64.
* `JaxRoundEngine.execute_rounds` — whole multi-round plans as one
  `lax.scan` over the round axis (used when no per-round replanning is
  required, i.e. everything except the BMF/MSRepair monitor loop —
  those route through `execute_round` between numpy replan steps).
  The per-round BMF monitor-and-replan itself stays on the *batched
  numpy* path (`optimize_round_batch`) rather than inside jit: its
  shapes are data-dependent by design — relay splices widen the hop
  axis mid-plan, the avail mask shrinks irreversibly, deep optima fall
  back to the scalar DFS — so only the fixed-shape event stepping
  crosses the jit boundary and the replan step reuses the exact code
  (and float behavior) the numpy backend is pinned by.
* `JaxPipelineEngine.execute` — PPT's pipeline stepper
  (`execute_pipeline_batch`'s twin): the topological min-scan unrolls
  the static depth levels inside the jitted while-loop body.

**Bandwidth epoch stacks.** The numpy engine refreshes a `(B, N, N)`
matrix stack lazily from each case's `BandwidthProcess`; a jitted loop
cannot call back into host rng, so epochs are *pre-sampled* into a
device-resident `(B, E, N, N)` tensor: recorded `BandwidthTrace` epochs
are used as-is, live processes are bulk-sampled with `sample_epochs`
(documented bit-identical to `matrix_at`), and static networks occupy a
single eternal epoch. A live case whose simulation outruns the sampled
horizon sets an overflow flag inside the loop; the engine then raises
`EpochHorizonError`, the caller restores any replan-mutated plans, the
horizon doubles, and the batch re-runs — with identical results, since
epoch matrices are pure functions of `(seed, epoch)`.

**Fan-in shares.** `IngressModel.share_weights` (Dirichlet splits) is
host rng too; with persistent shares the split is a pure function of
`(seed, receiver, fan-in)`, so the engine precomputes a
`(B, N, M + 1, M)` weight table covering every receiver that can see
fan-in >= 2 (a sound bound read off the compiled plans: concurrent
fan-in at a node never exceeds its per-round receiver-hop count, and
BMF relay splices only add fan-in-1 receivers). Non-persistent ingress
models fall back to the numpy engine.

**Bucketing + program reuse.** jit re-compiles per input shape, so the
adapters pad every batch axis (B, T, H, R, E, pipeline edges) up to the
next power of two with masked-out padding (zero-hop transfers, drained
edges, eternal-epoch bandwidth rows). Batches with differing round
counts therefore share one compiled program per (N, rounds-bucket) —
the cluster size N is the only raw shape dimension. Buffer donation is
enabled on non-CPU backends only (CPU XLA cannot consume donations and
would warn on every call).

Everything runs under `jax.enable_x64(True)` so device floats
are the same float64 ops the numpy engine performs; the parity suite
(`tests/test_jax_engine.py`) pins all 8 schemes x 3 volatility regimes
to the reference engines at 1e-6 relative tolerance with identical
round counts.
"""
from __future__ import annotations

import functools
import types
import typing

import numpy as np

_EPS = 1e-9
_GUARD = 100_000
# device epoch stacks are capped; a batch that cannot fit falls back to
# the numpy engine rather than thrashing host memory
_MEM_LIMIT_BYTES = 256 * 1024 * 1024
_INITIAL_LIVE_EPOCHS = 64
_MAX_LIVE_EPOCHS = 8192


class EpochHorizonError(RuntimeError):
    """A live case outran the pre-sampled bandwidth epoch horizon."""


class JaxUnsupported(RuntimeError):
    """The batch cannot run on the jax engine (caller falls back)."""


def _pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the shape-bucketing unit."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _x64():
    import jax

    return jax.enable_x64(True)


# ------------------------------------------------------------ jitted programs
_FNS: types.SimpleNamespace | None = None


def _build_fns() -> types.SimpleNamespace:
    import jax
    import jax.numpy as jnp
    from jax import lax

    # CPU XLA cannot consume donated buffers (it would warn per call).
    # On accelerators only t0 is donated, and only where an output of
    # its shape can take its buffer (the per-round and pipeline end
    # times): a donation no output can use is refused with a warning.
    donate_t0 = jax.default_backend() != "cpu"

    def epoch_state(t, ctx):
        """(bw, epoch_end, epoch) for every case at its own time `t` —
        the jit twin of `_BatchBandwidth.refresh` (recompute instead of
        refresh-on-crossing; epoch matrices are constant per epoch, so
        the values are identical)."""
        e_f = jnp.floor(t / ctx.interval)   # floor of true division ==
        e = e_f.astype(jnp.int64)           # BandwidthTrace.epoch_of
        idx = jnp.where(ctx.cycle, e % ctx.num_ep,
                        jnp.minimum(e, ctx.num_ep - 1))
        idx = jnp.clip(idx, 0, ctx.stack.shape[1] - 1)
        bw = ctx.stack[jnp.arange(ctx.stack.shape[0]), idx]
        return bw, (e_f + 1.0) * ctx.interval, e

    def fanin_rates(bw, u, v, act, ctx):
        """Contended rates for active (u -> v) pairs: the dense twin of
        `_group_structure` + `_contended_rates_grouped`. Group membership
        becomes a `(B, T, N)` one-hot match, in-group position a cumsum
        (same transfer-index order as the numpy stable sort), the group
        cap a masked max-reduction; the m == 1 degenerate case falls out
        of the same expression (weight 1, factor >= 1)."""
        B, N = bw.shape[0], bw.shape[1]
        bi = jnp.arange(B)[:, None]
        s = bw[bi, u, v]
        match = act[:, :, None] & (v[:, :, None] == jnp.arange(N))
        m_recv = match.sum(axis=1)                                 # (B, N)
        m_t = jnp.take_along_axis(m_recv, v, axis=1)               # (B, T)
        pos = jnp.take_along_axis(jnp.cumsum(match, axis=1),
                                  v[:, :, None], axis=2)[:, :, 0] - 1
        smax = jnp.max(jnp.where(match, s[:, :, None], -jnp.inf), axis=1)
        factor = jnp.maximum(ctx.floor[:, None],
                             1.0 - ctx.degrade[:, None] * (m_recv - 1))
        cap = jnp.take_along_axis(smax * factor, v, axis=1)
        w = ctx.shares[bi, v,
                       jnp.minimum(m_t, ctx.shares.shape[2] - 1),
                       jnp.clip(pos, 0, ctx.shares.shape[3] - 1)]
        return jnp.minimum(s, w * cap), s

    def round_events(hop_u, hop_v, n_hops, t0, ctx):
        """One round's event loop: the `execute_round_batch` while loop
        with the same per-iteration ops (refresh, rates, dt, debit,
        completion) over the whole padded batch."""
        B, T, H = hop_u.shape
        chunk_col = ctx.chunk[:, None]
        eps_chunk = _EPS * chunk_col

        def done_mask(hop_i):
            return (hop_i >= n_hops).all(axis=1)

        def cond(st):
            t, hop_i, left, ovf, it = st
            return (~done_mask(hop_i)).any() & (it < _GUARD)

        def body(st):
            t, hop_i, left, ovf, it = st
            done = done_mask(hop_i)
            bw, epoch_end, e = epoch_state(t, ctx)
            ovf = ovf | (ctx.can_ovf & ~done & (e >= ctx.num_ep)).any()
            act = hop_i < n_hops
            h = jnp.minimum(hop_i, H - 1)[:, :, None]
            u = jnp.take_along_axis(hop_u, h, axis=2)[:, :, 0]
            v = jnp.take_along_axis(hop_v, h, axis=2)[:, :, 0]
            eff, _ = fanin_rates(bw, u, v, act, ctx)
            rates = jnp.where(act, jnp.maximum(eff, 0.0), 0.0)
            cand = jnp.where(act & (rates > 0),
                             left / jnp.where(rates > 0, rates, 1.0),
                             jnp.inf)
            dt = jnp.minimum(epoch_end - t, cand.min(axis=1))
            dt = jnp.where(jnp.isfinite(dt) & (dt > 0), dt, _EPS)
            dt = jnp.where(done, 0.0, dt)
            left = left - rates * dt[:, None]
            t = t + dt
            compl = act & (left <= eps_chunk)
            hop_i = hop_i + compl
            left = jnp.where(compl, chunk_col, left)
            return t, hop_i, left, ovf, it + 1

        init = (t0, jnp.zeros((B, T), jnp.int64),
                jnp.broadcast_to(chunk_col, (B, T)),
                jnp.bool_(False), jnp.int64(0))
        t, hop_i, _, ovf, it = lax.while_loop(cond, body, init)
        return t, ovf, it, done_mask(hop_i).all()

    run_round = jax.jit(round_events,
                        donate_argnums=(3,) if donate_t0 else ())

    def rounds_scan(hop_u, hop_v, n_hops, t0, ctx):
        """All rounds of a batch as one `lax.scan` over the (padded)
        round axis; padding rounds have zero transfers and pass t
        through unchanged."""

        def step(carry, inp):
            t, ovf, mx, ok = carry
            hu, hv, nh = inp
            t2, o2, it, done = round_events(hu, hv, nh, t, ctx)
            return (t2, ovf | o2, jnp.maximum(mx, it), ok & done), t2

        init = (t0, jnp.bool_(False), jnp.int64(0), jnp.bool_(True))
        (_, ovf, mx, ok), tends = lax.scan(step, init,
                                           (hop_u, hop_v, n_hops))
        return tends, ovf, mx, ok

    run_rounds = jax.jit(rounds_scan)

    @functools.lru_cache(maxsize=None)
    def pipeline(dmax: int):
        """PPT pipeline stepper for a given tree depth (the depth-level
        min-scan unrolls statically, like the numpy `range(dmax, 0, -1)`
        loop in `execute_pipeline_batch`)."""

        def pipeline_events(child, parent, depth, left0, t0, ctx):
            B, E = child.shape
            N = ctx.stack.shape[2]
            chunk_col = ctx.chunk[:, None]
            bi = jnp.arange(B)[:, None]

            def cond(st):
                t, left, ovf, it = st
                return (left > _EPS * chunk_col).any() & (it < _GUARD)

            def body(st):
                t, left, ovf, it = st
                live = left > _EPS * chunk_col
                case_on = live.any(axis=1)
                bw, epoch_end, e = epoch_state(t, ctx)
                ovf = ovf | (ctx.can_ovf & case_on & (e >= ctx.num_ep)).any()
                rx_eff, s = fanin_rates(bw, child, parent, live, ctx)
                has_rx = (live[:, :, None]
                          & (parent[:, :, None] == jnp.arange(N))).any(axis=1)
                has_tx = (live[:, :, None]
                          & (child[:, :, None] == jnp.arange(N))).any(axis=1)
                rx_dup = jnp.where(jnp.take_along_axis(has_tx, parent, axis=1),
                                   ctx.duplex[:, None], 1.0)
                tx_dup = jnp.where(jnp.take_along_axis(has_rx, child, axis=1),
                                   ctx.duplex[:, None], 1.0)
                raw = jnp.minimum(jnp.maximum(rx_eff * rx_dup, 0.0),
                                  jnp.maximum(s * tx_dup, 0.0))
                raw_full = jnp.where(live, raw, 0.0)

                # iterative topological min-scan, deepest edges first
                node_supply = jnp.full((B, N), jnp.inf)
                eff = raw_full
                for d in range(dmax, 0, -1):
                    sel = live & (depth == d)
                    val = jnp.minimum(raw_full, node_supply[bi, child])
                    eff = jnp.where(sel, val, eff)
                    node_supply = node_supply.at[bi, parent].min(
                        jnp.where(sel, val, jnp.inf))
                rates = jnp.where(live, eff, 0.0)

                cand = jnp.where(live & (rates > 0),
                                 left / jnp.where(rates > 0, rates, 1.0),
                                 jnp.inf)
                dt = jnp.minimum(epoch_end - t, cand.min(axis=1))
                dt = jnp.where(jnp.isfinite(dt) & (dt > 0), dt, _EPS)
                dt = jnp.where(case_on, dt, 0.0)
                left = jnp.where(live, left - rates * dt[:, None], left)
                return t + dt, left, ovf, it + 1

            init = (t0, left0, jnp.bool_(False), jnp.int64(0))
            t, left, ovf, it = lax.while_loop(cond, body, init)
            return t, ovf, it, ~(left > _EPS * chunk_col).any()

        return jax.jit(pipeline_events,
                       donate_argnums=(4,) if donate_t0 else ())

    return types.SimpleNamespace(run_round=run_round,
                                 run_rounds=run_rounds,
                                 pipeline=pipeline)


def _fns() -> types.SimpleNamespace:
    global _FNS
    if _FNS is None:
        _FNS = _build_fns()
    return _FNS


# --------------------------------------------------------------- host engines
class _Ctx(typing.NamedTuple):
    """Pytree of per-batch device arrays (shapes use the padded Bp)."""

    stack: typing.Any      # (Bp, Ep, N, N) epoch matrices
    interval: typing.Any   # (Bp,) epoch length, inf = static network
    num_ep: typing.Any     # (Bp,) valid epochs in the stack
    cycle: typing.Any      # (Bp,) trace cycles (vs clamps) past the end
    can_ovf: typing.Any    # (Bp,) live case: sampled horizon can overflow
    chunk: typing.Any      # (Bp,)
    degrade: typing.Any    # (Bp,)
    floor: typing.Any      # (Bp,)
    duplex: typing.Any     # (Bp,)
    shares: typing.Any     # (Bp, N, M + 1, M) Dirichlet fan-in splits


class _EngineBase:
    """Shared device context: epoch stacks, ingress params, shares table."""

    def __init__(self, scenarios, num_nodes: int, need: np.ndarray,
                 mmax: int):
        if any(not sc.ingress.persistent_shares for sc in scenarios):
            # epoch-keyed share redraws cannot be pretabulated
            raise JaxUnsupported("non-persistent ingress shares")
        self.scenarios = list(scenarios)
        self.B = len(self.scenarios)
        self.Bp = _pow2(self.B)
        self.N = int(num_nodes)
        self.live_epochs = _INITIAL_LIVE_EPOCHS
        self._shares = self._shares_table(need, int(mmax))
        self._chunk = self._padded([sc.chunk_mb for sc in self.scenarios], 1.0)
        self._degrade = self._padded(
            [sc.ingress.degrade for sc in self.scenarios], 0.0)
        self._floor = self._padded(
            [sc.ingress.floor for sc in self.scenarios], 1.0)
        self._duplex = self._padded(
            [sc.ingress.duplex for sc in self.scenarios], 1.0)
        self._rebuild_ctx()

    def _padded(self, vals, fill: float) -> np.ndarray:
        out = np.full(self.Bp, fill, dtype=float)
        out[: self.B] = vals
        return out

    def _shares_table(self, need: np.ndarray, mmax: int) -> np.ndarray:
        """(Bp, N, mmax + 1, mmax) Dirichlet weight table; slot
        [b, v, m, i] is sender i's share of an m-way fan-in at receiver
        v. m <= 1 slots are 1.0 (the degenerate group)."""
        m1 = max(mmax + 1, 2)
        W = np.zeros((self.Bp, self.N, m1, max(mmax, 1)))
        W[:, :, :, 0] = 1.0
        cache: dict = {}
        for b, sc in enumerate(self.scenarios):
            ing = sc.ingress
            for v in np.nonzero(need[b])[0]:
                for m in range(2, m1):
                    key = (ing.seed, ing.alpha, int(v), m)
                    ww = cache.get(key)
                    if ww is None:
                        ww = ing.share_weights(m, int(v), 0)
                        cache[key] = ww
                    W[b, int(v), m, :m] = ww
        return W

    def _rebuild_ctx(self) -> None:
        """(Re)build the device epoch stack at the current live horizon."""
        from repro.core.bandwidth import BandwidthTrace

        interval = np.full(self.Bp, np.inf)
        num_ep = np.ones(self.Bp, dtype=np.int64)
        cycle = np.zeros(self.Bp, dtype=bool)
        can = np.zeros(self.Bp, dtype=bool)
        per: list[np.ndarray] = []
        for b, sc in enumerate(self.scenarios):
            bwp = sc.bw
            if type(bwp) is BandwidthTrace:
                ep = np.asarray(bwp.epochs)
                interval[b] = bwp.change_interval
                cycle[b] = bwp.cycle
                num_ep[b] = ep.shape[0]
            elif bwp.change_interval is None or (
                    bwp.mode == "jitter" and bwp.jitter == 0.0):
                ep = np.asarray(bwp.base)[None]
            else:
                # bit-identical to matrix_at for epochs [0, live_epochs);
                # memoized on the process instance, so every scheme/batch
                # replaying this case shares one sampling pass
                ep = bwp.epochs_prefix(self.live_epochs)
                interval[b] = bwp.change_interval
                num_ep[b] = self.live_epochs
                can[b] = True
            per.append(ep)
        self._can_grow = bool(can.any())
        emax = _pow2(max((e.shape[0] for e in per), default=1))
        if self.Bp * emax * self.N * self.N * 8 > _MEM_LIMIT_BYTES:
            raise JaxUnsupported("epoch stack exceeds the device budget")
        stack = np.zeros((self.Bp, emax, self.N, self.N))
        for b, ep in enumerate(per):
            n = ep.shape[1]
            stack[b, : ep.shape[0], :n, :n] = ep
        with _x64():
            import jax.numpy as jnp

            self.ctx = _Ctx(*(
                jnp.asarray(a) for a in (
                    stack, interval, num_ep, cycle, can, self._chunk,
                    self._degrade, self._floor, self._duplex, self._shares,
                )))

    def grow(self):
        """Double the live-epoch horizon after an `EpochHorizonError`.
        Returns self, or None when the horizon/memory cap is hit (the
        caller then falls back to the numpy engine)."""
        if not self._can_grow or self.live_epochs * 2 > _MAX_LIVE_EPOCHS:
            return None
        self.live_epochs *= 2
        try:
            self._rebuild_ctx()
        except JaxUnsupported:
            return None
        return self

    def _finish(self, t_end, ovf, it, done) -> np.ndarray:
        t_end = np.asarray(t_end)
        if bool(ovf):
            raise EpochHorizonError(
                f"simulation outran the {self.live_epochs}-epoch horizon")
        if not bool(done):
            raise RuntimeError("simulator failed to converge")
        return t_end[: self.B]


class JaxRoundEngine(_EngineBase):
    """Round-scheme executor: drop-in for `execute_round_batch` (per
    round, between host replan steps) plus a whole-plan scan fast path."""

    def __init__(self, scenarios, num_nodes: int, arrays):
        need, mmax = _round_fanin(arrays, num_nodes, len(scenarios))
        super().__init__(scenarios, num_nodes, need, mmax)

    def _pad_round(self, hop_u, hop_v, n_hops, t0):
        B, T, H = hop_u.shape
        Tp, Hp = _pow2(T), _pow2(H)
        hu = np.zeros((self.Bp, Tp, Hp), dtype=np.int64)
        hv = np.zeros((self.Bp, Tp, Hp), dtype=np.int64)
        nh = np.zeros((self.Bp, Tp), dtype=np.int64)
        hu[:B, :T, :H] = hop_u
        hv[:B, :T, :H] = hop_v
        nh[:B, :T] = n_hops
        tt = np.zeros(self.Bp)
        tt[:B] = t0
        return hu, hv, nh, tt

    def execute_round(self, hop_u, hop_v, n_hops, t0) -> np.ndarray:
        hu, hv, nh, tt = self._pad_round(hop_u, hop_v, n_hops, t0)
        with _x64():
            import jax.numpy as jnp

            out = _fns().run_round(jnp.asarray(hu), jnp.asarray(hv),
                                   jnp.asarray(nh), jnp.asarray(tt), self.ctx)
        return self._finish(*out)

    def execute_rounds(self, hop_all_u, hop_all_v, n_hops_all,
                       t0) -> tuple[np.ndarray, np.ndarray]:
        """(round_times (R, B), t_end (B,)) for whole plans in one scan."""
        B, R, T, H = hop_all_u.shape
        if R == 0:
            return np.zeros((0, B)), np.asarray(t0, dtype=float).copy()
        Rp, Tp, Hp = _pow2(R), _pow2(T), _pow2(H)
        hu = np.zeros((Rp, self.Bp, Tp, Hp), dtype=np.int64)
        hv = np.zeros((Rp, self.Bp, Tp, Hp), dtype=np.int64)
        nh = np.zeros((Rp, self.Bp, Tp), dtype=np.int64)
        hu[:R, :B, :T, :H] = hop_all_u.transpose(1, 0, 2, 3)
        hv[:R, :B, :T, :H] = hop_all_v.transpose(1, 0, 2, 3)
        nh[:R, :B, :T] = n_hops_all.transpose(1, 0, 2)
        tt = np.zeros(self.Bp)
        tt[:B] = t0
        with _x64():
            import jax.numpy as jnp

            tends, ovf, mx, ok = _fns().run_rounds(
                jnp.asarray(hu), jnp.asarray(hv), jnp.asarray(nh),
                jnp.asarray(tt), self.ctx)
            tends = np.asarray(tends)
        self._finish(tends[-1], ovf, mx, ok)
        tends = tends[:, : B]
        rt = np.diff(np.concatenate([np.asarray(t0)[None, :], tends[:R]],
                                    axis=0), axis=0)
        return rt, tends[R - 1].copy()


class JaxPipelineEngine(_EngineBase):
    """PPT executor: drop-in for `execute_pipeline_batch`."""

    def __init__(self, scenarios, num_nodes: int, parent, edge_valid):
        need, mmax = _pipeline_fanin(parent, edge_valid, num_nodes)
        super().__init__(scenarios, num_nodes, need, mmax)

    def execute(self, child, parent, depth, edge_valid, t0) -> np.ndarray:
        B, E = child.shape
        Ep = _pow2(E)
        c = np.zeros((self.Bp, Ep), dtype=np.int64)
        p = np.zeros((self.Bp, Ep), dtype=np.int64)
        d = np.zeros((self.Bp, Ep), dtype=np.int64)
        left0 = np.zeros((self.Bp, Ep))
        c[:B, :E] = child
        p[:B, :E] = parent
        d[:B, :E] = depth
        left0[:B, :E] = np.where(edge_valid, self._chunk[:B, None], 0.0)
        tt = np.zeros(self.Bp)
        tt[:B] = t0
        dmax = int(depth.max()) if depth.size else 0
        with _x64():
            import jax.numpy as jnp

            out = _fns().pipeline(dmax)(
                jnp.asarray(c), jnp.asarray(p), jnp.asarray(d),
                jnp.asarray(left0), jnp.asarray(tt), self.ctx)
        return self._finish(*out)


# ----------------------------------------------------------- fan-in analysis
def _round_fanin(arrays, num_nodes: int,
                 B: int) -> tuple[np.ndarray, int]:
    """(need (B, N) bool, mmax): receivers that can see fan-in >= 2 and
    the batch-wide fan-in bound, read off the compiled plans. Concurrent
    fan-in at a node never exceeds its per-round receiver-hop count, and
    BMF relay splices only add fan-in-1 relay receivers, so counts taken
    before replanning stay a sound bound."""
    need = np.zeros((B, num_nodes), dtype=bool)
    mmax = 1
    for b, pa in enumerate(arrays):
        if not pa.num_transfers:
            continue
        counts = np.diff(pa.round_start).astype(np.int64)
        rid = np.repeat(np.arange(pa.num_rounds), counts)
        cols = np.arange(pa.t_path.shape[1])
        recv_sel = ((cols[None, :] >= 1)
                    & (cols[None, :] < pa.t_path_len[:, None]))
        keys = (rid[:, None] * num_nodes + pa.t_path)[recv_sel]
        cnt = np.bincount(keys, minlength=pa.num_rounds * num_nodes)
        cnt = cnt.reshape(pa.num_rounds, num_nodes)
        need[b] = (cnt >= 2).any(axis=0)
        mmax = max(mmax, int(cnt.max(initial=1)))
    return need, mmax


def _pipeline_fanin(parent, edge_valid,
                    num_nodes: int) -> tuple[np.ndarray, int]:
    B = parent.shape[0]
    need = np.zeros((B, num_nodes), dtype=bool)
    mmax = 1
    for b in range(B):
        cnt = np.bincount(parent[b][edge_valid[b]], minlength=num_nodes)
        need[b] = cnt >= 2
        mmax = max(mmax, int(cnt.max(initial=1)))
    return need, mmax


# ------------------------------------------------------------------ factories
def make_round_engine(scenarios, num_nodes: int, arrays):
    """A `JaxRoundEngine` for the batch, or None when it must fall back
    to the numpy engine (non-persistent shares, memory cap)."""
    try:
        return JaxRoundEngine(scenarios, num_nodes, arrays)
    except JaxUnsupported:
        return None


def make_pipeline_engine(scenarios, num_nodes: int, parent, edge_valid):
    """A `JaxPipelineEngine` for the batch, or None (numpy fallback)."""
    try:
        return JaxPipelineEngine(scenarios, num_nodes, parent, edge_valid)
    except JaxUnsupported:
        return None
