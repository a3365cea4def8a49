"""Public jit'd entry points for the EC data plane.

Dispatches to the Pallas kernels: compiled on TPU, and run by the Pallas
interpreter (`interpret=True`) on any other backend, which is how the
tests exercise the kernel bodies under `JAX_PLATFORMS=cpu`. The batched
entry points always run the compiled kernels on TPU and default to the
numpy oracles in `repro.kernels.ref` elsewhere. Byte-level convenience
wrappers handle bit-slicing at the boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.ec import bitplane
from repro.kernels import ref
from repro.kernels.gf256_matmul import gf256_matmul_planes, gf256_scale_planes
from repro.kernels.xor_reduce import xor_reduce_groups_words, xor_reduce_words


@functools.lru_cache(maxsize=1)
def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _batched_path(use_kernel: bool | None,
                  interpret: bool | None) -> tuple[bool, bool]:
    """(use_kernel, interpret) for the batched entry points.

    On TPU they always run the compiled kernels: a TPU run that took the
    numpy oracle or the Pallas interpreter would verify and time other
    code than the device path, so asking for either there raises.
    Elsewhere the numpy oracles in `repro.kernels.ref` are the default —
    the batched paths are sized for throughput and the Pallas interpreter
    is not a performance proxy — and tests may ask for the kernels, which
    then run interpreted."""
    if not _interpret_default():
        if use_kernel is False or interpret:
            raise ValueError(
                "on TPU the batched data plane runs the compiled kernels "
                f"only (got use_kernel={use_kernel}, interpret={interpret})")
        return True, False
    return bool(use_kernel), True if interpret is None else interpret


def gf256_matmul(
    coeff: np.ndarray,
    data: jax.Array,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """(m, k) uint8 GF coefficients x (k, nbytes) uint8 -> (m, nbytes) uint8.

    The workhorse of RS encode / decode / repair-term premultiplication.
    `coeff` must be concrete (it parametrizes the bit-matrix masks).
    """
    coeff = np.asarray(coeff, dtype=np.uint8)
    if not use_kernel:
        return ref.gf256_matmul_bytes_ref(coeff, data)
    interpret = _interpret_default() if interpret is None else interpret
    nbytes = data.shape[-1]
    masks = jnp.asarray(bitplane.coeff_to_masks_np(coeff))
    planes = bitplane.pack_jnp(data)
    out_planes = gf256_matmul_planes(masks, planes, interpret=interpret)
    return bitplane.unpack_jnp(out_planes, nbytes)


def xor_reduce(
    chunks: jax.Array, *, use_kernel: bool = True, interpret: bool | None = None
) -> jax.Array:
    """(k, nbytes) uint8 -> (nbytes,) uint8 XOR of all chunks."""
    if chunks.shape[0] == 1:
        return chunks[0]
    if not use_kernel:
        out = chunks[0]
        for i in range(1, chunks.shape[0]):
            out = out ^ chunks[i]
        return out
    interpret = _interpret_default() if interpret is None else interpret
    nbytes = chunks.shape[-1]
    pad = -nbytes % 4
    if pad:
        chunks = jnp.pad(chunks, ((0, 0), (0, pad)))
    words = jax.lax.bitcast_convert_type(
        chunks.reshape(chunks.shape[0], -1, 4), jnp.uint32
    ).reshape(chunks.shape[0], -1)
    out = xor_reduce_words(words, interpret=interpret)
    out_bytes = jax.lax.bitcast_convert_type(out[:, None], jnp.uint8).reshape(-1)
    return out_bytes[:nbytes]


def gf256_scale_batch(
    coeffs: np.ndarray,
    data,
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """(M,) uint8 coeffs x (M, nbytes) uint8 -> (M, nbytes): row i scaled
    by its own coefficient.

    The batched data-plane premultiply: one call covers every
    (job, helper) chunk of a plan batch. On TPU it runs the compiled
    Pallas kernel; elsewhere `use_kernel=None` (the default) takes the
    numpy oracle (`ref.gf256_scale_batch_np`), see `_batched_path`. The
    kernel path drives `gf256_scale_planes` over an (M, W/block) grid —
    the same kernel body as `gf256_matmul`, one grid row per chunk
    instead of one `pallas_call` per chunk. Returns numpy on the ref
    path, a device array on the kernel path.
    """
    coeffs = np.asarray(coeffs, dtype=np.uint8).reshape(-1)
    use_kernel, interpret = _batched_path(use_kernel, interpret)
    if coeffs.size == 0 or not use_kernel:
        return ref.gf256_scale_batch_np(coeffs, np.asarray(data))
    nbytes = data.shape[-1]
    masks = jnp.asarray(bitplane.coeff_to_masks_np(coeffs[:, None]))
    planes = bitplane.pack_jnp(jnp.asarray(data))
    out_planes = gf256_scale_planes(masks, planes, interpret=interpret)
    return bitplane.unpack_jnp(out_planes, nbytes)


def xor_reduce_segments(
    chunks,
    groups: np.ndarray,
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """(T, nbytes) uint8 chunks + (G, Kmax) int row-index groups (-1
    padded) -> (G, nbytes): XOR-fold of each group's member rows.

    The batched data-plane merge: group g holds the payload rows arriving
    at one (case, destination) in a round. On TPU it runs the compiled
    kernel; elsewhere `use_kernel=None` takes `ref.xor_reduce_segments_np`
    (see `_batched_path`). The kernel path gathers groups to a dense
    (G, Kmax, W) word tensor (index -1 reads an all-zero row — XOR
    identity) and drives the `xor_reduce` kernel body over a (G, W/block)
    grid. Returns numpy on the ref path, a device
    array on the kernel path.
    """
    groups = np.asarray(groups, dtype=np.int64)
    use_kernel, interpret = _batched_path(use_kernel, interpret)
    if groups.shape[0] == 0 or not use_kernel:
        return ref.xor_reduce_segments_np(np.asarray(chunks), groups)
    chunks = jnp.asarray(chunks)
    t, nbytes = chunks.shape
    pad = -nbytes % 4
    if pad:
        chunks = jnp.pad(chunks, ((0, 0), (0, pad)))
    words = jax.lax.bitcast_convert_type(
        chunks.reshape(t, -1, 4), jnp.uint32
    ).reshape(t, -1)
    words = jnp.concatenate(
        [words, jnp.zeros((1, words.shape[1]), dtype=jnp.uint32)])
    gathered = words[jnp.where(groups >= 0, groups, t)]   # (G, Kmax, W)
    out = xor_reduce_groups_words(gathered, interpret=interpret)
    out_bytes = jax.lax.bitcast_convert_type(
        out, jnp.uint8).reshape(groups.shape[0], -1)
    return out_bytes[:, :nbytes]


def rs_encode(parity_coeff: np.ndarray, data_blocks: jax.Array) -> jax.Array:
    """(n-k, k) coeffs x (k, nbytes) data -> (n-k, nbytes) parity."""
    return gf256_matmul(parity_coeff, data_blocks)


def rs_reconstruct(repair_coeff: np.ndarray, helper_blocks: jax.Array) -> jax.Array:
    """(f, k) repair coeffs x (k, nbytes) helpers -> (f, nbytes) lost blocks."""
    return gf256_matmul(repair_coeff, helper_blocks)
