"""Closed-loop byte repair: batches of stripes through `execute_plans_batch`.

Set-up: plans for the deployment's failure mix, made by the program's
planners (`run_sweep(keep_plans=True)` on host scenarios of the
deployment's cluster, block size and bandwidth regime) from the mix's
fixed `plan_seed`; a pool of stripes placed over the cluster by
`place_stripes` and cut into fixed batches of `repair_concurrency`
stripes; and the pool's bytes, drawn from `--seed` and encoded on the
device by `gfref.encode_device`. So every seed does the same work, with
other bytes and in another order, and runs the same shapes: every batch
runs once in set-up, and nothing compiles once the window opens.

The window keeps one batch in flight: a batch is submitted when the last
one's reconstructed bytes are back on the host, as one DataNode's
reconstruction workers take stripes from a queue. It cycles through the
pool's batches, in an order drawn from the seed, until `seconds` have
passed.

The check, after the window: every stripe of every batch must return its
lost blocks and move the bytes its plan's hops say; a sample of the
batches, drawn from the seed, has every block compared byte for byte with
the bytes the benchmark generated (`gfref`), not with the program's own
`verified` flag.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from seams import patched

SAMPLED_BATCHES = 64      # batches kept for the byte comparison


def _seed32(seed: int, *tags: int) -> int:
    return int(np.random.SeedSequence([seed, *tags]).generate_state(1)[0]
               & 0x7FFFFFFF)


def mix_counts(mix: dict[str, float], pool: int) -> dict[str, int]:
    """Stripes per failure pattern: the mix's shares of `pool`, rounded,
    with the remainder on the largest share. The same for every seed."""
    counts = {p: int(round(share * pool)) for p, share in mix.items()}
    top = max(mix, key=mix.get)
    counts[top] += pool - sum(counts.values())
    return counts


def bytes_moved(plan, nbytes: int) -> int:
    """Relay-aware bytes a plan moves: one cell per hop of every transfer."""
    return nbytes * sum(len(t.path) - 1
                        for r in plan.rounds for t in r.transfers)


class RepairWorkload:
    """One repair cell: set-up in the constructor, then `window`, `check`."""

    def __init__(self, deployment: dict, traffic: dict, seed: int, *,
                 platform: str | None = "tpu", repair=None):
        self.dep = deployment
        self.seed = seed
        self.platform = platform
        code = deployment["code"]
        self.k = code["data_blocks"]
        self.n = self.k + code["parity_blocks"]
        self.nodes = deployment["cluster_nodes"]
        self.nbytes = deployment["cell_bytes"]
        self.per_batch = deployment["repair_concurrency"]
        pool = traffic["pool_stripes"]
        if pool % self.per_batch:
            raise ValueError("the pool must split into whole batches")

        plan_seed = traffic["plan_seed"]
        plans = self._plans(mix_counts(deployment["failure_mix"], pool),
                            plan_seed)
        mixed = np.random.default_rng(plan_seed).permutation(pool)
        self.plans = [plans[i] for i in mixed]
        self._place()
        self.codewords = self._encode(pool)
        self.batches = [list(range(s, s + self.per_batch))
                        for s in range(0, pool, self.per_batch)]
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        self.order = [int(b) for b in rng.permutation(len(self.batches))]
        self.batch_lost = [self.nbytes * sum(len(self.plans[s].jobs)
                                             for s in b)
                           for b in self.batches]
        self.repair_fn = repair or program_repair
        self._warm()

    # ------------------------------------------------------------ set-up
    def _plans(self, counts: dict[str, int], plan_seed: int) -> list:
        from repro.sim.suite import MonteCarloSuite, SampleSpace
        from repro.sim.sweep import run_sweep

        d = self.dep
        bw = d["bandwidth"]
        plans = []
        for tag, (pattern, count) in enumerate(sorted(counts.items())):
            if not count:
                continue
            scheme = d["plan_schemes"][pattern]
            space = SampleSpace(
                codes=((self.n, self.k),), cluster_sizes=(self.nodes,),
                chunk_mb=(float(d["block_mb"]),), regimes=(bw["regime"],),
                failure_patterns=(pattern,), bw_low=float(bw["low_MBps"]),
                bw_high=float(bw["high_MBps"]))
            suite = MonteCarloSuite(f"{d['name']}.{pattern}", count, space,
                                    schemes=(scheme,),
                                    base_seed=_seed32(plan_seed, tag))
            sweep = run_sweep(suite, executor="serial", keep_plans=True)
            plans += [case.results[scheme].plan for case in sweep.cases]
        return plans

    def _place(self) -> None:
        from repro.core.engine.arrays import compile_plan, relabel_plan_nodes
        from repro.ec.rs import RSCode
        from repro.ec.stripe import place_stripes

        self.code = RSCode(self.n, self.k)
        stripes = place_stripes(len(self.plans), self.code, self.nodes)
        self.compiled = [relabel_plan_nodes(compile_plan(p),
                                            s.perm(self.nodes))
                         for p, s in zip(self.plans, stripes)]
        self.block_maps = [s.block_map(self.nodes) for s in stripes]
        # planner node ids below n are block positions (the simulator's
        # convention), so a job's lost block is its failed node id
        self.expected = [{j.job_id: j.failed_node for j in p.jobs}
                         for p in self.plans]
        self.expected_moved = [bytes_moved(p, self.nbytes)
                               for p in self.plans]

    def _encode(self, pool: int) -> np.ndarray:
        import jax

        import gfref

        key = jax.random.key(_seed32(self.seed, 3))
        cw = gfref.encode_device(key, pool, self.n, self.k, self.nbytes)
        return np.asarray(cw)

    def repair(self, b: int):
        """(per-stripe {job id: bytes}, per-stripe bytes moved) of batch b."""
        return self.repair_fn(self, b)

    def _warm(self) -> None:
        """Run every batch once. On the chip, both GF steps must hand back
        device arrays of that platform: a numpy answer means the batch
        left the device."""
        import jax

        from repro.kernels import ops

        bad: list[str] = []

        def on_device(name):
            def wrap(fn):
                def inner(*a, **kw):
                    out = fn(*a, **kw)
                    if not (isinstance(out, jax.Array) and {
                            d.platform for d in out.devices()}
                            == {self.platform}):
                        bad.append(name)
                    return out
                return inner
            return wrap

        with contextlib.ExitStack() as stack:
            if self.platform is not None:
                for name in ("gf256_scale_batch", "xor_reduce_segments"):
                    stack.enter_context(
                        patched(ops, name, on_device(name)))
            for b in range(len(self.batches)):
                self.repair(b)
        if bad:
            raise RuntimeError(
                f"{len(bad)} GF(256) calls returned no {self.platform} "
                f"device array ({sorted(set(bad))}): the batch left the chip")

    # ------------------------------------------------------------ window
    @contextlib.contextmanager
    def seams(self, calls: list[dict]):
        """Context for a traced window: each GF(256) step runs inside a
        named host span and is waited for there, and `calls` gets the
        bytes of its arguments that live on the host (not `jax.Array`s)
        and its algorithmic bytes. The data plane waits for both results
        itself right after the call, so the wait adds no time of its
        own."""
        import jax

        import roofline
        from repro.kernels import ops

        def seam(op, alg_bytes):
            def wrap(fn):
                def inner(x, y, **kw):
                    with jax.profiler.TraceAnnotation(
                            f"bench.{op}#{len(calls)}"):
                        out = jax.block_until_ready(fn(x, y, **kw))
                    calls.append(dict(
                        op=op, alg_bytes=alg_bytes(x, y),
                        h2d_bytes=sum(int(a.nbytes) for a in (x, y)
                                      if not isinstance(a, jax.Array))))
                    return out
                return inner
            return wrap

        premultiply = seam("premultiply", lambda coeffs, data:
                           roofline.premultiply_bytes(*data.shape))
        fold = seam("fold", lambda chunks, groups: roofline.fold_bytes(
            chunks.shape[0], len(groups), chunks.shape[1]))
        with patched(ops, "gf256_scale_batch", premultiply), \
                patched(ops, "xor_reduce_segments", fold):
            yield

    def window(self, seconds: float, *, traced: bool = False) -> dict:
        """Run the closed loop for `seconds`; return the end-to-end
        numbers and keep what the check needs."""
        import jax

        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 4]))
        self.sample: list[tuple[int, int, list]] = []
        self.results: list[tuple[int, list, np.ndarray]] = []
        lat: list[float] = []
        lost = 0
        i = 0
        span = (jax.profiler.TraceAnnotation if traced
                else lambda _: contextlib.nullcontext())
        start = time.perf_counter()
        deadline = start + seconds
        while True:
            b = self.order[i % len(self.order)]
            with span("bench.batch"):
                t = time.perf_counter()
                recon, moved = self.repair(b)
                done = time.perf_counter()
            lat.append(done - t)
            lost += self.batch_lost[b]
            self.results.append((b, [set(r) for r in recon],
                                 np.asarray(moved)))
            # reservoir sample of whole batches for the byte comparison
            if len(self.sample) < SAMPLED_BATCHES:
                self.sample.append((i, b, recon))
            else:
                j = int(rng.integers(i + 1))
                if j < SAMPLED_BATCHES:
                    self.sample[j] = (i, b, recon)
            i += 1
            if done >= deadline:
                break
        elapsed = done - start
        self.window_s = elapsed
        self.lost_bytes = lost
        return {
            "repair_MBps": lost / 1e6 / elapsed,
            "repair_p90_ms": 1e3 * float(np.percentile(lat, 90)),
            "batches": len(lat),
            "stripes": len(lat) * self.per_batch,
            "mean_ms": 1e3 * float(np.mean(lat)),
            # mean batch time in each tenth of the window's batches: a
            # slow start would mean that something warms up inside it
            "tenths_ms": ",".join(f"{1e3 * float(np.mean(t)):.1f}"
                                  for t in np.array_split(lat, 10)
                                  if len(t)),
        }

    # ------------------------------------------------------------- check
    def check(self) -> tuple[list[tuple[str, float, float]], int, int]:
        """Compare what the window produced; returns the numbers compared
        with their limits, the stripes attempted and those that failed."""
        failed_stripes: set[tuple[int, int]] = set()
        missing = moved_off = 0
        for i, (b, keys, moved) in enumerate(self.results):
            for pos, s in enumerate(self.batches[b]):
                want = self.expected[s]
                if keys[pos] != set(want):
                    missing += len(set(want) - keys[pos])
                    failed_stripes.add((i, pos))
                if int(moved[pos]) != self.expected_moved[s]:
                    moved_off += 1
                    failed_stripes.add((i, pos))
        wrong = 0
        for i, b, recon in self.sample:
            for pos, s in enumerate(self.batches[b]):
                cw = self.codewords[s]
                for job, block in self.expected[s].items():
                    got = recon[pos].get(job)
                    if got is None:
                        continue            # counted as missing above
                    if not np.array_equal(got, cw[block]):
                        wrong += 1
                        failed_stripes.add((i, pos))
        checks = [("wrong_blocks", wrong, 0), ("missing_blocks", missing, 0),
                  ("bytes_moved_off", moved_off, 0)]
        return checks, len(self.results) * self.per_batch, len(failed_stripes)


def program_repair(wl: RepairWorkload, b: int):
    """Batch b through the program's batched data plane."""
    from repro.core.engine.dataplane import execute_plans_batch

    idx = wl.batches[b]
    res = execute_plans_batch(
        [wl.compiled[s] for s in idx], wl.code,
        [wl.codewords[s] for s in idx],
        block_of=[wl.block_maps[s] for s in idx])
    return res.reconstructed, res.bytes_moved


def workload(deployment: dict, traffic: dict, seed: int, **kw):
    return RepairWorkload(deployment, traffic, seed, **kw)
