"""Plain GF(2^8) Reed-Solomon arithmetic, written apart from the program.

The benchmark makes its stripes with this module and judges the program's
reconstructed blocks by it, so it imports nothing of `repro`. The field is
GF(2^8) over x^8 + x^4 + x^3 + x^2 + 1 (0x11d, the polynomial of Jerasure and
ISA-L). An (n, k) code is systematic: with V[i, j] = (i + 1)^j, the n x k
generator is V times the inverse of V's top k x k block, so its top k rows
are the identity. A lost block f is rebuilt from k helper blocks H as
G[f] . inv(G[H]) . blocks[H].
"""
from __future__ import annotations

import numpy as np

POLY = 0x11D


def mul_slow(a: int, b: int) -> int:
    """Shift-and-add product in GF(2^8): the ground truth of the tables."""
    r = 0
    while b:
        if b & 1:
            r ^= a
        a <<= 1
        if a & 0x100:
            a ^= POLY
        b >>= 1
    return r


def _mul_table() -> np.ndarray:
    t = np.zeros((256, 256), dtype=np.uint8)
    for a in range(256):
        for b in range(a, 256):
            t[a, b] = t[b, a] = mul_slow(a, b)
    return t


MUL = _mul_table()
INV = np.zeros(256, dtype=np.uint8)
INV[np.nonzero(MUL == 1)[0]] = np.nonzero(MUL == 1)[1]


def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(m, k) x (k, p) over GF(2^8)."""
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for j in range(a.shape[1]):
        out ^= MUL[a[:, j][:, None], b[j][None, :]]
    return out


def mat_inv(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inverse of a square matrix over GF(2^8)."""
    n = m.shape[0]
    aug = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for c in range(n):
        piv = next((r for r in range(c, n) if aug[r, c]), None)
        if piv is None:
            raise ValueError("singular matrix over GF(2^8)")
        aug[[c, piv]] = aug[[piv, c]]
        aug[c] = MUL[INV[aug[c, c]], aug[c]]
        for r in range(n):
            if r != c and aug[r, c]:
                aug[r] ^= MUL[aug[r, c], aug[c]]
    return aug[:, n:]


def generator(n: int, k: int) -> np.ndarray:
    """(n, k) systematic generator of the RS(n, k) code."""
    pw = np.ones((n, k), dtype=np.uint8)
    for i in range(n):
        for j in range(1, k):
            pw[i, j] = MUL[pw[i, j - 1], i + 1]
    return mat_mul(pw, mat_inv(pw[:k]))


def repair_row(n: int, k: int, failed: int, helpers) -> np.ndarray:
    """(k,) coefficients: lost block `failed` = sum_i c_i * block[helpers[i]]."""
    g = generator(n, k)
    return mat_mul(g[failed][None, :], mat_inv(g[list(helpers)]))[0]


def scale(c: int, x: np.ndarray) -> np.ndarray:
    """c * x for a byte array x."""
    return MUL[c][x]


def combine(coeffs, blocks) -> np.ndarray:
    """XOR_i coeffs[i] * blocks[i] over byte arrays."""
    out = np.zeros_like(blocks[0])
    for c, blk in zip(coeffs, blocks):
        out ^= scale(int(c), blk)
    return out


def encode_np(data: np.ndarray, n: int) -> np.ndarray:
    """(k, nbytes) data blocks -> (n, nbytes) codeword, on the host."""
    k = data.shape[0]
    g = generator(n, k)
    parity = [combine(g[r], data) for r in range(k, n)]
    return np.concatenate([data, np.stack(parity)]) if parity else data


def encode_device(key, pool: int, n: int, k: int, nbytes: int):
    """(pool, n, nbytes) uint8 codewords of random data, made on the device.

    One jitted program draws the data from `key` and computes the parity
    by doubling (x * 2 = x << 1, reduced by 0x1d when bit 7 was set): the
    product c * x is the XOR of x * 2^b over the set bits b of c. Each data
    block's doublings feed the parity accumulators as they are made, so
    the device holds little beyond the codewords.
    """
    import jax
    import jax.numpy as jnp

    g = generator(n, k)

    def build(key):
        data = jax.random.bits(key, (pool, k, nbytes), jnp.uint8)
        parity = [jnp.zeros((pool, nbytes), jnp.uint8) for _ in range(k, n)]
        for j in range(k):
            x = data[:, j]
            for b in range(8):
                for r in range(k, n):
                    if int(g[r, j]) >> b & 1:
                        parity[r - k] = parity[r - k] ^ x
                if b < 7:
                    x = (x << 1) ^ ((x >> 7) * jnp.uint8(0x1D))
        return jnp.concatenate([data, jnp.stack(parity, axis=1)], axis=1)

    return jax.jit(build)(key)
