"""Data-plane execution of repair plans, byte-verified.

The simulator times a plan; this module *runs* it. Two paths share the
semantics:

* `execute_plan` — the serial oracle below: every helper holds a real
  chunk, scaled by its Galois coefficient (`ops.gf256_scale_batch`, one
  call per job), transfers move buffers between per-node stores, and
  merges XOR (`ops.xor_reduce_segments`). Relay nodes only buffer (the
  paper: forwarding nodes do not compute). At the end the requestor's
  buffer must equal the lost block bit-for-bit.
* `execute_plans_batch` (re-exported from
  `repro.core.engine.dataplane`) — the batched engine: a whole batch of
  compiled `PlanArrays` in one compact `(U, nbytes)` store, a row per
  (case, slot) key, with all rounds executed as gather → GF(256)
  premultiply → segment-XOR array steps through the same two entry
  points of `repro.kernels.ops`. Byte-identical to running the oracle
  case by case (`tests/test_dataplane.py` pins it); the oracle stays the
  reference this facade keeps readable.

Both run what `repro.kernels.ops` chooses for the platform: the
compiled Pallas kernels on TPU, the numpy references elsewhere.

**Invariant (both paths):** plans must be `validate_plan`-clean. The
executors implement store-and-forward faithfully — a source's buffer is
consumed when it sends, so a plan whose transfer sources a node that
already forwarded its fragment (or never held one) is *unexecutable*;
both paths raise `ValueError` on it rather than moving zeros.
`run_scheme` validates every plan it simulates, so every simulator-
produced plan satisfies this by construction.

`bytes_moved` counts the paper's real network cost: a relayed transfer
re-sends the whole chunk on every hop, so a path of length L moves
`(L - 1) * nbytes` bytes (store-and-forward, no computation at relays).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.engine.dataplane import (BatchExecutionResult,
                                         execute_plans_batch,
                                         identity_block_map)
from repro.core.plan import Job, RepairPlan
from repro.ec.rs import RSCode
from repro.kernels import ops

__all__ = [
    "ExecutionResult",
    "execute_plan",
    "BatchExecutionResult",
    "execute_plans_batch",
    "identity_block_map",
]


@dataclasses.dataclass
class ExecutionResult:
    reconstructed: dict[int, np.ndarray]   # job_id -> bytes
    verified: bool
    bytes_moved: int


def execute_plan(
    plan: RepairPlan,
    code: RSCode,
    codeword: np.ndarray,                  # (n, nbytes) original stripe
    *,
    block_of: np.ndarray | None = None,
) -> ExecutionResult:
    """Serial oracle: walk one validated plan over real bytes.

    `block_of[node]` maps node ids to codeword block positions (identity
    when None — the simulator convention that node i holds block i); the
    sweep's byte-verification layer passes a real stripe placement
    (`repro.ec.stripe`) instead.
    """
    nbytes = codeword.shape[1]
    if block_of is None:
        nodes = [x for j in plan.jobs
                 for x in (j.failed_node, *j.helpers)] + [0]
        block_of = identity_block_map(max(nodes) + 1, code.n)
    block_of = np.asarray(block_of, dtype=np.int64)
    # per-(job, node) payload store
    store: dict[tuple[int, int], jnp.ndarray] = {}
    for job in plan.jobs:
        if block_of[job.failed_node] < 0 or any(
                block_of[h] < 0 for h in job.helpers):
            # -1 must not wrap into python negative indexing — that would
            # "repair" the wrong block and self-consistently verify it
            raise ValueError(
                f"job {job.job_id}: a failed/helper node holds no block "
                "under the given placement")
        coeffs = code.repair_coeffs(
            tuple([int(block_of[job.failed_node])]),
            tuple(int(block_of[h]) for h in job.helpers),
        )[0]  # (k,) coefficients, aligned with job.helpers
        pre = ops.gf256_scale_batch(
            coeffs, codeword[block_of[list(job.helpers)]])
        for h, row in zip(job.helpers, pre):
            store[(job.job_id, h)] = row

    bytes_moved = 0
    for ri, rnd in enumerate(plan.rounds):
        arrivals: list[tuple[int, int, jnp.ndarray]] = []
        for t in rnd.transfers:
            # store-and-forward: sending consumes the buffer, so a source
            # drained in an earlier round cannot feed this one — only
            # validate_plan-clean plans are executable (module docstring)
            payload = store.pop((t.job, t.src), None)
            if payload is None:
                raise ValueError(
                    f"round {ri}: transfer {t} sources node {t.src} which "
                    f"holds no buffer for job {t.job} (consumed in an "
                    "earlier round?) — execute_plan requires a "
                    "validate_plan-clean plan")
            bytes_moved += nbytes * (len(t.path) - 1)   # relays re-send
            arrivals.append((t.job, t.dst, payload))
        for job_id, dst, payload in arrivals:
            existing = store.get((job_id, dst))
            if existing is None:
                store[(job_id, dst)] = payload
            else:
                store[(job_id, dst)] = ops.xor_reduce_segments(
                    jnp.stack([existing, payload]), np.array([[0, 1]]))[0]

    recon: dict[int, np.ndarray] = {}
    ok = True
    for job in plan.jobs:
        held = store.get((job.job_id, job.requestor))
        if held is None:
            recon[job.job_id] = np.zeros(nbytes, dtype=np.uint8)
            ok = False
            continue
        got = np.asarray(held)
        recon[job.job_id] = got
        if not np.array_equal(got, codeword[block_of[job.failed_node]]):
            ok = False
    return ExecutionResult(reconstructed=recon, verified=ok, bytes_moved=bytes_moved)
