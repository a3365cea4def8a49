"""Batched byte data plane: execute compiled `PlanArrays` over real bytes.

This is the array-native twin of `repro.core.executor.execute_plan` — the
module that *runs* a repair plan instead of timing it. Where the serial
oracle walks one plan's transfers with a dict of per-node device buffers
and one kernel call per chunk, this engine lowers a whole batch of
compiled plans into one compact `(U, nbytes)` store — a row per (case,
slot) key the batch touches; slot `j * N + v` is node v's buffer for job
j — and executes every round as three array steps:

1. **GF(256) premultiply** (init round only) — every helper chunk,
   handed to `kernels.ops.stage_rows` as the row of the caller's codeword
   where it lies (on the kernel path it goes up from there, with no host
   copy, and the device stacks the batch), scaled by its repair
   coefficient in one `kernels.ops.gf256_scale_batch` call,
   with the coefficients themselves computed batched by
   `RSCode.repair_coeffs_batch` (one lockstep Gauss-Jordan per code); the
   result becomes the store's first rows;
2. **gather** — all of the round's payload rows, batch-wide, taken out
   of the store into a `(T_r, nbytes)` array;
3. **segment-XOR** — arrivals folded per (case, destination) group by one
   `kernels.ops.xor_reduce_segments` call; one update consumes the
   round's sources and XORs the folded rows into their destinations.

On TPU the two ops run Pallas kernels over a grid (one `pallas_call`
per step instead of one per chunk): the premultiply scales the staged
bytes as they are, by shift-and-add over GF(256)'s polynomial on uint32
words (`kernels.gf256_scale`, no bit planes), and the fold XORs words;
everywhere else `kernels.ops` runs the numpy references in
`repro.kernels.ref` instead. The store follows the premultiply's
answer: a device array on the kernel path, where the round state stays
on the device and only the rebuilt blocks come back in one copy, and a
host array, updated in place, on the ref path. The host keeps only the
index plan and the rows' occupancy.

Execution semantics match the serial oracle exactly: within a round all
sources are consumed before any arrival lands (store-and-forward
two-phase), fan-in arrivals XOR-fold in transfer order (XOR is
associative, so the fold order cannot matter), relays re-send whole
buffers (`bytes_moved` counts `nbytes * (path_len - 1)` per transfer).
Like the oracle, the engine assumes a `validate_plan`-clean plan; the one
runtime invariant it re-checks is source occupancy — a transfer whose
source buffer was consumed in an earlier round raises `ValueError`
instead of silently moving zeros.

`block_of` decouples node ids from codeword positions: the simulator
convention (node i holds block i) is the identity default, while the
sweep's byte-verification layer passes the mapping of a *placed* stripe
(`repro.ec.stripe`), with plans relabeled through the placement by
`arrays.relabel_plan_nodes`.
"""
from __future__ import annotations

import dataclasses
import functools
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.engine.arrays import PlanArrays, compile_plan
from repro.core.plan import RepairPlan
from repro.ec.rs import RSCode
from repro.kernels import ops


@dataclasses.dataclass
class BatchExecutionResult:
    """Per-case outcome of one batched data-plane run."""

    reconstructed: list[dict[int, np.ndarray]]   # per case: job_id -> bytes
    verified: np.ndarray                         # (B,) bool — every job exact
    bytes_moved: np.ndarray                      # (B,) int64

    @property
    def all_verified(self) -> bool:
        return bool(self.verified.all())


def identity_block_map(num_nodes: int, n: int) -> np.ndarray:
    """The simulator's placement: node i holds block i (i < n), -1 after."""
    out = np.full(max(num_nodes, n), -1, dtype=np.int64)
    out[:n] = np.arange(n)
    return out


def _as_plan_arrays(plans) -> list[PlanArrays]:
    return [p if isinstance(p, PlanArrays) else compile_plan(p)
            for p in plans]


def _repair_coeffs(
    pas: list[PlanArrays],
    codes: list[RSCode],
    block_maps: list[np.ndarray],
) -> list[np.ndarray]:
    """(k,)-coefficient rows for every (case, job), batched per code.

    Jobs of all cases sharing one (n, k) code go through a single
    `repair_coeffs_batch` call (one lockstep Gauss-Jordan), and identical
    (failed, helpers) rows within it are deduplicated — a 64-stripe batch
    repairing the same logical failure computes its coefficients once.
    """
    by_code: dict[tuple[int, int], list[tuple[int, int]]] = {}
    for b, (pa, code) in enumerate(zip(pas, codes)):
        for j in range(pa.num_jobs):
            by_code.setdefault((code.n, code.k), []).append((b, j))
    out: list[list] = [[None] * pa.num_jobs for pa in pas]
    for (n, k), rows in by_code.items():
        code = next(c for c in codes if (c.n, c.k) == (n, k))
        failed = np.empty(len(rows), dtype=np.int64)
        helpers = np.empty((len(rows), k), dtype=np.int64)
        for i, (b, j) in enumerate(rows):
            pa, bmap = pas[b], block_maps[b]
            hl = int(pa.job_helpers_len[j])
            if hl != k:
                raise ValueError(
                    f"job {int(pa.job_id[j])} has {hl} helpers, "
                    f"RS({n},{k}) repair needs exactly k")
            hb = bmap[pa.job_helpers[j, :k]]
            fb = bmap[pa.job_failed[j]]
            if fb < 0 or (hb < 0).any():
                raise ValueError(
                    f"job {int(pa.job_id[j])}: a failed/helper node holds "
                    "no block under the given placement")
            failed[i] = fb
            helpers[i] = hb
        uniq, inv = np.unique(
            np.concatenate([failed[:, None], helpers], axis=1),
            axis=0, return_inverse=True)
        coeffs = code.repair_coeffs_batch(uniq[:, 0], uniq[:, 1:])[inv]
        for i, (b, j) in enumerate(rows):
            out[b][j] = coeffs[i]
    return [np.stack(rows) if rows else np.zeros((0, 0), np.uint8)
            for rows in out]


def execute_plans_batch(
    plans: Sequence[PlanArrays | RepairPlan],
    codes: RSCode | Sequence[RSCode],
    codewords: np.ndarray | Sequence[np.ndarray],
    *,
    block_of: Sequence[np.ndarray | None] | None = None,
) -> BatchExecutionResult:
    """Execute a batch of repair plans over real bytes and verify them.

    `plans` are `PlanArrays` (or `RepairPlan`s, compiled on entry),
    `codes` one shared or per-case `RSCode`, `codewords` per-case
    `(n, nbytes)` uint8 block stacks (block-indexed; same nbytes across
    the batch). `block_of[b][node]` maps node ids to block positions
    (identity when None — the simulator convention). The GF(256) steps
    run what `kernels.ops` chooses for the platform: the compiled Pallas
    kernels on TPU, the numpy references elsewhere. Returns per-case
    reconstructed bytes, a verified flag (every job's requestor buffer
    equals the lost block bit-for-bit) and relay-aware `bytes_moved` —
    byte-identical to running `executor.execute_plan` case by case.
    """
    with spans.span("repro.dataplane.batch"):
        return _execute_plans_batch(plans, codes, codewords, block_of)


def _pull(x) -> np.ndarray:
    """The rebuilt blocks on the host, copied inside the d2h span; its
    `bytes` counts device arrays only (0 for the numpy path's)."""
    with spans.span("repro.dataplane.d2h"):
        spans.count("bytes", x.nbytes if isinstance(x, jax.Array) else 0)
        return np.asarray(x, dtype=np.uint8)


# The store on the device is (U, width / 128, 128) uint8, each row a
# contiguous run of the TPU's tiles: in a 2-D (U, nbytes) array a row is
# spread 128 bytes to a tile, and row copies ran 6-12x slower on a TPU
# v5e. The updates go row by row, since XLA's TPU gather and scatter of
# whole 1 MiB rows unroll into megabytes of code per shape. One program
# per shape each; `_fold_in_device` donates the store, so no round
# copies it.
_LANES = 128


def _tiled(x):
    """(n, nbytes) -> (n, width / 128, 128), zero-padded to the width."""
    x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % _LANES)))
    return x.reshape(x.shape[0], -1, _LANES)


@functools.partial(jax.jit, static_argnums=1)
def _grow_device(pre, rows):
    return jnp.pad(_tiled(pre), ((0, rows - pre.shape[0]), (0, 0), (0, 0)))


@functools.partial(jax.jit, static_argnums=2)
def _take_device(store, rows, nbytes):
    out = jax.lax.map(
        lambda i: jax.lax.dynamic_index_in_dim(store, i, keepdims=False),
        rows)
    return out.reshape(rows.shape[0], -1)[:, :nbytes]


@functools.partial(jax.jit, donate_argnums=0)
def _fold_in_device(store, src, dst, folded):
    zero = jnp.zeros((1,) + store.shape[1:], store.dtype)
    folded = _tiled(folded)

    def consume(k, st):
        return jax.lax.dynamic_update_slice_in_dim(st, zero, src[k], 0)

    def add(k, st):
        row = (jax.lax.dynamic_slice_in_dim(st, dst[k], 1)
               ^ jax.lax.dynamic_slice_in_dim(folded, k, 1))
        return jax.lax.dynamic_update_slice_in_dim(st, row, dst[k], 0)

    store = jax.lax.fori_loop(0, src.shape[0], consume, store)
    return jax.lax.fori_loop(0, dst.shape[0], add, store)


def _grow(pre, rows: int):
    """The store: the premultiplied rows first, zero rows after, where
    the premultiply answered (the device, or host memory)."""
    if isinstance(pre, jax.Array):
        return _grow_device(pre, rows)
    store = np.zeros((rows, pre.shape[1]), dtype=np.uint8)
    store[:len(pre)] = pre
    return store


def _take(store, rows: np.ndarray, nbytes: int):
    """The store's `rows`, as a new (len(rows), nbytes) array beside it."""
    if isinstance(store, jax.Array):
        return _take_device(store, rows, nbytes)
    return store[rows]


def _fold_in(store, src: np.ndarray, dst: np.ndarray, folded):
    """Consume the round's source rows (zeroed: two-phase), then XOR each
    folded row into its destination row (an empty row is zeros, the XOR
    identity). Returns the updated store."""
    if isinstance(store, jax.Array):
        return _fold_in_device(store, src, dst, folded)
    store[src] = 0
    store[dst] ^= np.asarray(folded)
    return store


def _execute_plans_batch(plans, codes, codewords,
                         block_of) -> BatchExecutionResult:
    """`execute_plans_batch`'s body, one span per step. A helper, so that
    the caller's batch span also holds the frees of its buffers."""
    with spans.span("repro.dataplane.prepare"):
        pas = _as_plan_arrays(plans)
        B = len(pas)
        if B == 0:
            return BatchExecutionResult([], np.zeros(0, bool),
                                        np.zeros(0, np.int64))
        codes = list(codes) if isinstance(codes, Sequence) else [codes] * B
        cws = [np.asarray(cw, dtype=np.uint8) for cw in codewords]
        if len(codes) != B or len(cws) != B:
            raise ValueError("plans, codes and codewords must align")
        nbytes = cws[0].shape[-1]
        if any(cw.shape[-1] != nbytes for cw in cws):
            raise ValueError("all codewords must share one chunk size")
        N = max(pa.num_nodes for pa in pas)
        block_maps = []
        for b, pa in enumerate(pas):
            bmap = None if block_of is None else block_of[b]
            if bmap is None:
                bmap = identity_block_map(max(N, codes[b].n), codes[b].n)
            else:
                bmap = np.asarray(bmap, dtype=np.int64)
                if bmap.size < N:
                    bmap = np.concatenate(
                        [bmap, np.full(N - bmap.size, -1, dtype=np.int64)])
            block_maps.append(bmap)
        S = max(pa.num_jobs for pa in pas) * N
        coeffs = _repair_coeffs(pas, codes, block_maps)

        # flat round-major transfer table across the batch
        fb = np.concatenate([np.full(pa.num_transfers, b, dtype=np.int64)
                             for b, pa in enumerate(pas)])
        fround = np.concatenate([
            np.repeat(np.arange(pa.num_rounds, dtype=np.int64),
                      np.diff(pa.round_start)) for pa in pas])
        fsrc = np.concatenate([pa.t_job_idx.astype(np.int64) * N + pa.t_src
                               for pa in pas])
        fdst = np.concatenate([pa.t_job_idx.astype(np.int64) * N + pa.t_dst
                               for pa in pas])
        fhops = np.concatenate([pa.t_path_len.astype(np.int64) - 1
                                for pa in pas])
        bytes_moved = np.zeros(B, dtype=np.int64)
        np.add.at(bytes_moved, fb, nbytes * fhops)

        # the compact store: one row per (case, slot) the batch touches,
        # keyed b * S + slot (slot j * N + v is node v's buffer for job
        # j); the premultiplied helper rows first, in staging order
        hkey, rkey = [], []
        for b, pa in enumerate(pas):
            for j in range(pa.num_jobs):
                hs = pa.job_helpers[j, :int(pa.job_helpers_len[j])]
                hkey.append(b * S + j * N + hs.astype(np.int64))
                rkey.append(b * S + j * N + int(pa.job_requestor[j]))
        keys = np.concatenate(
            [*hkey, fb * S + fsrc, fb * S + fdst, np.asarray(rkey, np.int64)])
        _, first = np.unique(keys, return_index=True)
        store_keys = keys[np.sort(first)]
        sorter = np.argsort(store_keys)

        def row_of(k: np.ndarray) -> np.ndarray:
            at = np.searchsorted(store_keys, k, sorter=sorter)
            return sorter[at].astype(np.int32)

        src_row, dst_row = row_of(fb * S + fsrc), row_of(fb * S + fdst)
        req_row = row_of(np.asarray(rkey, np.int64))
        occupied = np.zeros(store_keys.size, dtype=bool)

    # ---- init: one batched premultiply of every helper chunk
    # views of the caller's codeword rows, no host copy: `ops.stage_rows`
    # stacks them where the premultiply runs, and `_pull` waits for a
    # result that depends on every row, so the caller may reuse them after
    with spans.span("repro.dataplane.stage"):
        tcoef, rows = [], []
        for b, pa in enumerate(pas):
            for j in range(pa.num_jobs):
                hs = pa.job_helpers[j, :int(pa.job_helpers_len[j])]
                tcoef.extend(coeffs[b][j])
                rows += [cws[b][blk] for blk in block_maps[b][hs]]
        staged = ops.stage_rows(rows) if rows else None
        spans.count("helper_bytes", len(rows) * nbytes)
        spans.count("device_rows",
                    len(rows) if isinstance(staged, jax.Array) else 0)
    pre = np.zeros((0, nbytes), dtype=np.uint8)
    if staged is not None:
        with spans.span("repro.dataplane.premultiply"):
            pre = ops.gf256_scale_batch(
                np.asarray(tcoef, dtype=np.uint8), staged)
        del staged              # the staged chunks must not outlive the call
    with spans.span("repro.dataplane.scatter"):
        store = _grow(pre, store_keys.size)
        occupied[:len(pre)] = True
        del pre

    R = max((pa.num_rounds for pa in pas), default=0)
    device_rounds = 0
    for r in range(R):
        with spans.span("repro.dataplane.round"):
            with spans.span("repro.dataplane.gather"):
                rows = np.nonzero(fround == r)[0]
                if not rows.size:
                    continue
                src = src_row[rows]
                if not occupied[src].all():
                    bad = rows[int(np.nonzero(~occupied[src])[0][0])]
                    job, node = divmod(int(fsrc[bad]), N)
                    raise ValueError(
                        f"round {r}: case {int(fb[bad])} transfer sources "
                        f"slot (job {job}, node {node}) which holds no "
                        "buffer — consumed in an earlier round? "
                        "execute_plans_batch requires a validate_plan-clean "
                        "plan")
                payload = _take(store, src, nbytes)  # gather (T_r, nbytes)
                occupied[src] = False                # two-phase consume
                # fan-in groups per destination row, transfer order kept
                dst = dst_row[rows]
                order = np.argsort(dst, kind="stable")
                sdst = dst[order]
                boundary = np.empty(order.size, dtype=bool)
                boundary[0] = True
                np.not_equal(sdst[1:], sdst[:-1], out=boundary[1:])
                starts = np.nonzero(boundary)[0]
                counts = np.diff(np.append(starts, order.size))
                groups = np.full((starts.size, int(counts.max())), -1,
                                 dtype=np.int64)
                pos = np.arange(order.size) - np.repeat(starts, counts)
                groups[np.repeat(np.arange(starts.size), counts), pos] = order
            with spans.span("repro.dataplane.fold"):
                folded = ops.xor_reduce_segments(payload, groups)
            with spans.span("repro.dataplane.accumulate"):
                gdst = sdst[starts]
                store = _fold_in(store, src, gdst, folded)
                occupied[gdst] = True
            device_rounds += isinstance(store, jax.Array)
    spans.count("device_rounds", device_rounds)
    spans.count("rounds", R)
    spans.count("jobs", req_row.size)

    # ---- one copy back: every job's requestor row
    with spans.span("repro.dataplane.gather"):
        rebuilt = _take(store, req_row, nbytes)
    rebuilt = _pull(rebuilt)
    with spans.span("repro.dataplane.verify"):
        recon: list[dict[int, np.ndarray]] = [dict() for _ in range(B)]
        verified = np.ones(B, dtype=bool)
        i = 0
        for b, pa in enumerate(pas):
            for j in range(pa.num_jobs):
                got = rebuilt[i]
                recon[b][int(pa.job_id[j])] = got
                fblock = int(block_maps[b][pa.job_failed[j]])
                if not (occupied[req_row[i]]
                        and np.array_equal(got, cws[b][fblock])):
                    verified[b] = False
                i += 1
    return BatchExecutionResult(reconstructed=recon, verified=verified,
                                bytes_moved=bytes_moved)
