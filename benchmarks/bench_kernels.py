"""EC data-plane kernel throughput on whatever device JAX runs on.

Every row names the platform and `device_kind` it ran on. Off TPU the
Pallas kernels run in the interpreter, and those rows say `interpret=`:
such a rate measures the interpreter, not a device. Roofline shares need
a peak table keyed by `device_kind`, which belongs to the on-chip
benchmark, not to this file.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row
from repro.ec.rs import RSCode
from repro.kernels import ops, ref


def _bench(fn, *args, reps=3):
    fn(*args)                      # warm up / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jnp.asarray(out).block_until_ready()
    return (time.perf_counter() - t0) / reps


def run() -> list[Row]:
    dev = jax.devices()[0]
    where = f"platform={dev.platform} device_kind={dev.device_kind!r}"
    mode = "interpret" if ops._interpret_default() else "compiled"
    rows = []
    rng = np.random.default_rng(0)
    for (n, k) in [(6, 3), (7, 4)]:
        code = RSCode(n, k)
        nbytes = 1 << 18
        data = jnp.asarray(
            rng.integers(0, 256, size=(k, nbytes), dtype=np.uint8))
        coeff = code.parity_coeffs()

        t_kernel = _bench(lambda: ops.rs_encode(coeff, data))
        t_ref = _bench(lambda: ref.gf256_matmul_bytes_ref(coeff, data))
        mbps = k * nbytes / t_kernel / 2**20
        rows.append(Row(
            f"kernels/rs{n}{k}_encode_256KBx{k}",
            t_kernel * 1e6,
            f"{mode}={mbps:.0f}MB/s ref_ratio={t_ref / t_kernel:.2f}x {where}",
        ))

    x = jnp.asarray(rng.integers(0, 256, size=(4, 1 << 19), dtype=np.uint8))
    t_x = _bench(lambda: ops.xor_reduce(x))
    rows.append(Row(
        "kernels/xor_reduce_512KBx4",
        t_x * 1e6,
        f"{mode}={2 / t_x:.0f}MB/s {where}",
    ))
    return rows
