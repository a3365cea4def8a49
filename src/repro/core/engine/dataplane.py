"""Batched byte data plane: execute compiled `PlanArrays` over real bytes.

This is the array-native twin of `repro.core.executor.execute_plan` — the
module that *runs* a repair plan instead of timing it. Where the serial
oracle walks one plan's transfers with a dict of per-node device buffers
and one kernel call per chunk, this engine lowers a whole batch of
compiled plans into dense buffer tensors and executes every round as
three array steps:

1. **gather** — all of the round's payload rows, batch-wide, out of a
   `(B, S, nbytes)` buffer tensor (S = jobs x nodes slots; slot
   `j * N + v` is node v's buffer for job j);
2. **GF(256) premultiply** (init round only) — every helper chunk scaled
   by its repair coefficient in one `kernels.ops.gf256_scale_batch` call,
   with the coefficients themselves computed batched by
   `RSCode.repair_coeffs_batch` (one lockstep Gauss-Jordan per code);
3. **segment-XOR** — arrivals folded per (case, destination) group by one
   `kernels.ops.xor_reduce_segments` call and XOR-scattered back.

On TPU the two ops drive the Pallas kernel bodies over a grid (one
`pallas_call` per step instead of one per chunk); everywhere else they
fall back to the numpy oracles in `repro.kernels.ref`, so the batched
path stays a genuine throughput win on CPU too (`benchmarks/
bench_dataplane.py` gates it).

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
from collections.abc import Sequence

import jax
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
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> BatchExecutionResult:
    """Execute a batch of repair plans over real bytes and verify them.

    `plans` are `PlanArrays` (or `RepairPlan`s, compiled on entry),
    `codes` one shared or per-case `RSCode`, `codewords` per-case
    `(n, nbytes)` uint8 block stacks (block-indexed; same nbytes across
    the batch). `block_of[b][node]` maps node ids to block positions
    (identity when None — the simulator convention). `use_kernel=None`
    compiles the Pallas kernels on TPU and runs the numpy ref path
    elsewhere (see `kernels.ops`). Returns per-case reconstructed bytes,
    a verified flag (every job's requestor buffer equals the lost block
    bit-for-bit) and relay-aware `bytes_moved` — byte-identical to
    running `executor.execute_plan` case by case.
    """
    with spans.span("repro.dataplane.batch"):
        return _execute_plans_batch(plans, codes, codewords, block_of,
                                    use_kernel, interpret)


def _pull(x) -> np.ndarray:
    """A GF(256) step's result on the host, copied inside the d2h span;
    its `bytes` counts device arrays only (0 for the numpy path's)."""
    with spans.span("repro.dataplane.d2h"):
        spans.count("bytes", x.nbytes if isinstance(x, jax.Array) else 0)
        return np.asarray(x, dtype=np.uint8)


def _execute_plans_batch(plans, codes, codewords, block_of, use_kernel,
                         interpret) -> BatchExecutionResult:
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
        jmax = max(pa.num_jobs for pa in pas)
        S = jmax * N
        buf = np.zeros((B, S, nbytes), dtype=np.uint8)
        occupied = np.zeros((B, S), dtype=bool)
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

    # ---- init: one batched premultiply of every helper chunk
    with spans.span("repro.dataplane.stage"):
        tb, tslot, tcoef, tdata = [], [], [], []
        for b, pa in enumerate(pas):
            for j in range(pa.num_jobs):
                hl = int(pa.job_helpers_len[j])
                hs = pa.job_helpers[j, :hl].astype(np.int64)
                tb.extend([b] * hl)
                tslot.extend(j * N + hs)
                tcoef.extend(coeffs[b][j])
                tdata.append(cws[b][block_maps[b][hs]])
        staged = np.concatenate(tdata) if tb else None
    if tb:
        with spans.span("repro.dataplane.premultiply"):
            pre = ops.gf256_scale_batch(
                np.asarray(tcoef, dtype=np.uint8), staged,
                use_kernel=use_kernel, interpret=interpret)
        del staged              # the staging copy must not outlive the call
        pre = _pull(pre)
        with spans.span("repro.dataplane.scatter"):
            buf[np.asarray(tb), np.asarray(tslot)] = pre
            occupied[np.asarray(tb), np.asarray(tslot)] = True

    R = max((pa.num_rounds for pa in pas), default=0)
    for r in range(R):
        with spans.span("repro.dataplane.gather"):
            rows = np.nonzero(fround == r)[0]
            if not rows.size:
                continue
            rb, rsrc, rdst = fb[rows], fsrc[rows], fdst[rows]
            if not occupied[rb, rsrc].all():
                bad = int(np.nonzero(~occupied[rb, rsrc])[0][0])
                raise ValueError(
                    f"round {r}: case {int(rb[bad])} transfer sources slot "
                    f"(job {int(rsrc[bad]) // N}, node {int(rsrc[bad]) % N}) "
                    "which holds no buffer — consumed in an earlier round? "
                    "execute_plans_batch requires a validate_plan-clean plan")
            payload = buf[rb, rsrc]                  # gather (T_r, nbytes)
            buf[rb, rsrc] = 0                        # two-phase consume
            occupied[rb, rsrc] = False
            # fan-in groups per (case, destination slot), transfer order kept
            key = rb * S + rdst
            order = np.argsort(key, kind="stable")
            skey = key[order]
            boundary = np.empty(order.size, dtype=bool)
            boundary[0] = True
            np.not_equal(skey[1:], skey[:-1], out=boundary[1:])
            starts = np.nonzero(boundary)[0]
            counts = np.diff(np.append(starts, order.size))
            groups = np.full((starts.size, int(counts.max())), -1,
                             dtype=np.int64)
            pos = np.arange(order.size) - np.repeat(starts, counts)
            groups[np.repeat(np.arange(starts.size), counts), pos] = order
        with spans.span("repro.dataplane.fold"):
            folded = ops.xor_reduce_segments(
                payload, groups, use_kernel=use_kernel, interpret=interpret)
        folded = _pull(folded)
        with spans.span("repro.dataplane.accumulate"):
            gkey = skey[starts]
            gb, gs = gkey // S, gkey % S
            buf[gb, gs] ^= folded                    # zeros when unoccupied
            occupied[gb, gs] = True

    # ---- verify every job's requestor buffer against the lost block
    with spans.span("repro.dataplane.verify"):
        recon: list[dict[int, np.ndarray]] = [dict() for _ in range(B)]
        verified = np.ones(B, dtype=bool)
        for b, pa in enumerate(pas):
            for j in range(pa.num_jobs):
                slot = j * N + int(pa.job_requestor[j])
                got = buf[b, slot].copy()
                recon[b][int(pa.job_id[j])] = got
                fblock = int(block_maps[b][pa.job_failed[j]])
                if not (occupied[b, slot]
                        and np.array_equal(got, cws[b][fblock])):
                    verified[b] = False
    return BatchExecutionResult(reconstructed=recon, verified=verified,
                                bytes_moved=bytes_moved)
