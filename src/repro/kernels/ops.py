"""Public entry points for the GF(256) steps of the EC data plane.

Two steps, one kernel each, and the staging of the first's input:

* `gf256_scale_batch` — every row of bytes times its own coefficient
  (`kernels.gf256_scale`, on the bytes as they are);
* `xor_reduce_segments` — XOR-fold of groups of rows
  (`kernels.xor_reduce`);
* `stage_rows` — host rows into one array where the premultiply runs.

`gf256_matmul` — and `rs_encode` / `rs_reconstruct` on it — is built from
the two: scale every (output, input) pair, then fold each output's k rows.

Which implementation computes a step is decided here, by
`_pallas_interpret` alone, from the platform: the compiled Pallas
kernels on TPU, the numpy references in `repro.kernels.ref` everywhere
else. A reference answers with a numpy array, a kernel with a device
array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.gf256_scale import gf256_scale_rows
from repro.kernels.xor_reduce import xor_reduce_groups_words


@functools.lru_cache(maxsize=1)
def _pallas_interpret() -> bool | None:
    """The `interpret` flag the Pallas kernels run with, or None where
    the numpy references run instead: False (compiled kernels) on TPU,
    None on every other backend. Tests patch it to answer True, which
    runs the kernel bodies in the Pallas interpreter."""
    return False if jax.default_backend() == "tpu" else None


@jax.jit
def _stack_rows(*rows):
    return jnp.stack(rows)


def stage_rows(rows):
    """(nbytes,) uint8 host rows -> one (M, nbytes) array, where
    `gf256_scale_batch` reads it.

    The kernel path hands every row to the device as it lies in the
    caller's memory, with no host copy, and one jitted program stacks
    them there (one program per row count). The reference path, which
    wants host bytes, makes one host concatenation. The rows must stay
    unchanged until the result has been computed.
    """
    if _pallas_interpret() is None:
        return np.stack(rows)
    return _stack_rows(*rows)


def gf256_scale_batch(coeffs: np.ndarray, data):
    """(M,) uint8 coeffs x (M, nbytes) uint8 -> (M, nbytes): row i scaled
    by its own coefficient.

    The batched data-plane premultiply: one call covers every
    (job, helper) chunk of a plan batch. The kernel path is one jitted
    program, `gf256_scale_rows`: `data` goes up as it is where it lives
    on the host (`stage_rows` gives it on the device), and the kernel
    scales its bytes directly, by shift-and-add over 0x11D on
    uint32 words, with per-row bit masks made on the device from
    `coeffs`. The reference is `ref.gf256_scale_batch_np`.
    """
    coeffs = np.asarray(coeffs, dtype=np.uint8).reshape(-1)
    interpret = _pallas_interpret()
    if coeffs.size == 0 or interpret is None:
        return ref.gf256_scale_batch_np(coeffs, np.asarray(data))
    return gf256_scale_rows(coeffs, data, interpret=interpret)


def xor_reduce_segments(chunks, groups: np.ndarray):
    """(T, nbytes) uint8 chunks + (G, Kmax) int row-index groups (-1
    padded) -> (G, nbytes): XOR-fold of each group's member rows.

    The batched data-plane merge: group g holds the payload rows arriving
    at one (case, destination) in a round. The kernel path gathers groups
    to a dense (G, Kmax, W) word tensor (index -1 reads an all-zero row —
    XOR identity) and drives the `xor_reduce` kernel body over a
    (G, W/block) grid. The reference is `ref.xor_reduce_segments_np`.
    """
    groups = np.asarray(groups, dtype=np.int64)
    interpret = _pallas_interpret()
    if groups.shape[0] == 0 or interpret is None:
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


def gf256_matmul(coeff: np.ndarray, data):
    """(m, k) uint8 GF coefficients x (k, nbytes) uint8 -> (m, nbytes).

    The workhorse of RS encode / decode: `gf256_scale_batch` scales the
    m * k (output, input) pairs, `xor_reduce_segments` folds each
    output's k rows.
    """
    coeff = np.asarray(coeff, dtype=np.uint8)
    m, k = coeff.shape
    return xor_reduce_segments(
        gf256_scale_batch(coeff.ravel(), jnp.tile(data, (m, 1))),
        np.arange(m * k).reshape(m, k))


def rs_encode(parity_coeff: np.ndarray, data_blocks: jax.Array) -> jax.Array:
    """(n-k, k) coeffs x (k, nbytes) data -> (n-k, nbytes) parity."""
    return gf256_matmul(parity_coeff, data_blocks)


def rs_reconstruct(repair_coeff: np.ndarray, helper_blocks: jax.Array) -> jax.Array:
    """(f, k) repair coeffs x (k, nbytes) helpers -> (f, nbytes) lost blocks."""
    return gf256_matmul(repair_coeff, helper_blocks)
