"""Erasure-coded sharded checkpoints with BMFRepair/MSRepair recovery.

Layout on disk:
  <dir>/step_<N>/manifest.json          treedef, shapes, dtypes, code, placement
  <dir>/step_<N>/domain_<d>.bin         every block placed on failure domain d

The flattened train-state blob is split into stripes of k chunk-sized data
blocks; n-k parity blocks per stripe come from `ops.rs_encode` (all
stripes in one batched call: the GF(256) scale and fold kernels on TPU).
Blocks are placed RAID-5-rotated across `num_domains` failure domains
(hosts or pods).

Losing up to n-k domains is repaired *in place*: the repair planner
(msrepair+bmf by default — the paper's algorithms; any baseline scheme can
be selected for ablation) produces the transfer schedule, the simulator
prices it under the cluster's bandwidth process (this is the number an
operator cares about: repair-time-to-restore redundancy), and the data
plane reconstructs the bytes with the RS kernel, verified by checksum.

Saves are double-buffered on a background thread (async checkpointing off
the training critical path); commits are atomic via manifest rename.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bandwidth import BandwidthProcess, IngressModel
from repro.core.simulator import RepairSimulator, Scenario, SimResult
from repro.ec import stripe as stripe_lib
from repro.ec.rs import RSCode
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class ECCheckpointConfig:
    directory: str
    n: int = 6
    k: int = 4
    chunk_bytes: int = 1 << 20          # 1 MiB blocks
    num_domains: int = 8
    scheme: str = "msrepair"            # repair planner for multi-failure
    single_scheme: str = "bmf"          # repair planner for single failure
    async_save: bool = True


@dataclasses.dataclass
class RepairReport:
    lost_domains: tuple[int, ...]
    stripes_repaired: int
    blocks_repaired: int
    sim: SimResult | None
    wall_seconds: float


class ECCheckpointer:
    def __init__(self, cfg: ECCheckpointConfig,
                 bw: BandwidthProcess | None = None,
                 ingress: IngressModel | None = None):
        self.cfg = cfg
        self.code = RSCode(cfg.n, cfg.k)
        self.bw = bw
        self.ingress = ingress or IngressModel()
        self._thread: threading.Thread | None = None
        os.makedirs(cfg.directory, exist_ok=True)

    # ------------------------------------------------------------------ save
    def _flatten(self, state) -> tuple[np.ndarray, dict]:
        leaves, treedef = jax.tree.flatten(state)
        arrs = [np.asarray(l) for l in leaves]
        meta = {
            "shapes": [list(a.shape) for a in arrs],
            "dtypes": [str(a.dtype) for a in arrs],
            "treedef": str(treedef),
        }
        blob = (np.concatenate([a.reshape(-1).view(np.uint8) for a in arrs])
                if arrs else np.zeros(0, np.uint8))
        return blob, meta

    def _unflatten(self, blob: np.ndarray, meta: dict, template) -> object:
        leaves, treedef = jax.tree.flatten(template)
        out, off = [], 0
        for shape, dtype in zip(meta["shapes"], meta["dtypes"]):
            nb = int(np.prod(shape)) * np.dtype(dtype).itemsize
            arr = blob[off: off + nb].view(np.dtype(dtype)).reshape(shape)
            out.append(jnp.asarray(arr))
            off += nb
        return jax.tree.unflatten(treedef, out)

    def save(self, step: int, state, *, wait: bool = False) -> str:
        """Encode + write. Async by default (double-buffered)."""
        blob, meta = self._flatten(state)
        if self._thread is not None:
            self._thread.join()                 # previous save must land
        if self.cfg.async_save and not wait:
            self._thread = threading.Thread(
                target=self._write, args=(step, blob, meta), daemon=True)
            self._thread.start()
        else:
            self._write(step, blob, meta)
        return self._step_dir(step)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.cfg.directory, f"step_{step:08d}")

    def _write(self, step: int, blob: np.ndarray, meta: dict) -> None:
        cfg, code = self.cfg, self.code
        chunks = stripe_lib.split_blob(blob, code.k, cfg.chunk_bytes)
        num_stripes = chunks.shape[0]
        # batched parity for ALL stripes in one kernel call:
        # (S, k, C) -> (k, S*C)
        data_k = np.ascontiguousarray(chunks.transpose(1, 0, 2)).reshape(
            code.k, -1)
        parity = np.asarray(ops.rs_encode(code.parity_coeffs(),
                                          jnp.asarray(data_k)))
        parity = parity.reshape(code.m, num_stripes, cfg.chunk_bytes
                                ).transpose(1, 0, 2)   # (S, m, C)
        blocks = np.concatenate([chunks, parity], axis=1)   # (S, n, C)
        stripes = stripe_lib.place_stripes(num_stripes, code, cfg.num_domains)

        d = self._step_dir(step)
        os.makedirs(d + ".tmp", exist_ok=True)
        per_domain: dict[int, list[tuple[int, int]]] = {}
        for s in stripes:
            for b, node in enumerate(s.node_ids):
                per_domain.setdefault(node, []).append((s.stripe_id, b))
        checksums = {}
        for dom, entries in per_domain.items():
            buf = np.concatenate([blocks[sid, b] for sid, b in entries])
            path = os.path.join(d + ".tmp", f"domain_{dom}.bin")
            buf.tofile(path)
            checksums[str(dom)] = zlib.crc32(buf.tobytes())
        manifest = {
            "step": step,
            "total_bytes": int(blob.size),
            "n": code.n, "k": code.k,
            "chunk_bytes": cfg.chunk_bytes,
            "num_stripes": num_stripes,
            "num_domains": cfg.num_domains,
            "checksums": checksums,
            **meta,
        }
        with open(os.path.join(d + ".tmp", "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(d):
            import shutil
            shutil.rmtree(d)
        os.rename(d + ".tmp", d)                # atomic commit

    # ------------------------------------------------------------------ load
    def latest_step(self) -> int | None:
        steps = [int(x.split("_")[1]) for x in os.listdir(self.cfg.directory)
                 if x.startswith("step_") and not x.endswith(".tmp")]
        return max(steps) if steps else None

    def _read_domains(self, d: str, manifest: dict,
                      lost: set[int]) -> dict[int, np.ndarray]:
        out = {}
        for dom in range(manifest["num_domains"]):
            if dom in lost:
                continue
            path = os.path.join(d, f"domain_{dom}.bin")
            if not os.path.exists(path):
                continue
            buf = np.fromfile(path, dtype=np.uint8)
            if zlib.crc32(buf.tobytes()) != manifest["checksums"].get(str(dom)):
                continue                        # corrupt domain == lost
            out[dom] = buf
        return out

    def load(self, template, *, step: int | None = None,
             lost_domains: tuple[int, ...] = ()) -> tuple[object, RepairReport]:
        """Restore train state; repair any blocks on lost domains."""
        cfg, code = self.cfg, self.code
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError("no checkpoint found")
        d = self._step_dir(step)
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        lost = set(lost_domains)
        domains = self._read_domains(d, manifest, lost)
        missing = set(range(manifest["num_domains"])) - set(domains)

        stripes = stripe_lib.place_stripes(
            manifest["num_stripes"], code, manifest["num_domains"])
        cb = manifest["chunk_bytes"]
        # domain files are ordered by (stripe, block) per _write
        per_domain_order: dict[int, list[tuple[int, int]]] = {}
        for s in stripes:
            for b, node in enumerate(s.node_ids):
                per_domain_order.setdefault(node, []).append((s.stripe_id, b))

        block_of: dict[tuple[int, int], np.ndarray] = {}
        for dom, buf in domains.items():
            for i, (sid, b) in enumerate(per_domain_order[dom]):
                block_of[(sid, b)] = buf[i * cb: (i + 1) * cb]

        t0 = time.time()
        stripes_repaired = blocks_repaired = 0
        sim_result = None
        for s in stripes:
            lost_blocks = [b for b in range(code.n)
                           if (s.stripe_id, b) not in block_of]
            lost_data = [b for b in lost_blocks if b < code.k]
            if not lost_data:
                continue
            if len(lost_blocks) > code.m:
                raise RuntimeError(
                    f"stripe {s.stripe_id}: {len(lost_blocks)} blocks lost, "
                    f"only {code.m} tolerable")
            helpers = [b for b in range(code.n) if b not in lost_blocks][: code.k]
            coeff = code.repair_coeffs(tuple(lost_data), tuple(helpers))
            hblocks = jnp.asarray(
                np.stack([block_of[(s.stripe_id, b)] for b in helpers]))
            rec = np.asarray(ops.rs_reconstruct(coeff, hblocks))
            for i, b in enumerate(lost_data):
                block_of[(s.stripe_id, b)] = rec[i]
                blocks_repaired += 1
            stripes_repaired += 1
            if sim_result is None and self.bw is not None:
                sim_result = self._price_repair(lost_blocks)

        blob = np.concatenate(
            [block_of[(s.stripe_id, b)] for s in stripes for b in range(code.k)]
        )[: manifest["total_bytes"]]
        state = self._unflatten(blob, manifest, template)
        report = RepairReport(
            lost_domains=tuple(sorted(missing)),
            stripes_repaired=stripes_repaired,
            blocks_repaired=blocks_repaired,
            sim=sim_result,
            wall_seconds=time.time() - t0,
        )
        return state, report

    def _price_repair(self, lost_blocks: list[int]) -> SimResult:
        """Price one stripe's repair under the cluster bandwidth process
        using the configured scheme (the paper's algorithms)."""
        cfg = self.cfg
        sc = Scenario(
            num_nodes=max(cfg.num_domains, self.code.n),
            code=self.code,
            failed=tuple(lost_blocks),
            bw=self.bw,
            ingress=self.ingress,
            chunk_mb=cfg.chunk_bytes / 2**20,
        )
        scheme = (cfg.single_scheme if len(lost_blocks) == 1 else cfg.scheme)
        return RepairSimulator(sc).run(scheme)
