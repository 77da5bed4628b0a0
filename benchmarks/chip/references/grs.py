"""Plain reference of a systematic Reed-Solomon code over F_q, in numpy
int64, independent of the program under test.

The configuration states the code by its evaluation points: data symbol
k is f(alpha_k) and parity symbol r is f(beta_r), for the one polynomial
f of degree < K through the K data symbols.  So

    parity_r = sum_k L_k(beta_r) * x_k          (mod q)

with L_k the Lagrange basis polynomial of the alphas, and a lost data
symbol is recovered by interpolating f through any K surviving symbols.
"""
from __future__ import annotations

import numpy as np


def lagrange_block(q: int, src, dst) -> np.ndarray:
    """(len(src), len(dst)) int64 block M with M[k, j] = L_k(dst[j]): the
    value at dst[j] of the polynomial of degree < len(src) that is 1 at
    src[k] and 0 at every other point of src."""
    src = [int(a) % q for a in src]
    dst = [int(b) % q for b in dst]
    out = np.zeros((len(src), len(dst)), np.int64)
    for k, ak in enumerate(src):
        for j, b in enumerate(dst):
            num = den = 1
            for i, ai in enumerate(src):
                if i != k:
                    num = num * (b - ai) % q
                    den = den * (ak - ai) % q
            out[k, j] = num * pow(den, q - 2, q) % q
    return out


def combine(q: int, block: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """(J, W) = block.T @ rows over F_q, with rows (S, W) int64 in [0, q).
    Each product is below q^2 < 2^33, so up to 2^30 terms sum exactly in
    int64 before the one reduction."""
    acc = np.zeros((block.shape[1], rows.shape[1]), np.int64)
    for s in range(block.shape[0]):
        acc += block[s][:, None] * rows[s][None, :]
    return acc % q


class Reference:
    """Encode and degraded read of the configuration's code."""

    def __init__(self, code: dict):
        self.q = int(code["field_modulus"])
        self.alphas = [int(a) for a in code["alphas"]]
        self.betas = [int(b) for b in code["betas"]]
        self.K, self.R = len(self.alphas), len(self.betas)
        self.A = lagrange_block(self.q, self.alphas, self.betas)

    def encode(self, x: np.ndarray) -> np.ndarray:
        """(K, W) data -> (R, W) parity."""
        return combine(self.q, self.A, np.asarray(x, np.int64) % self.q)

    def read(self, v: np.ndarray, lost) -> np.ndarray:
        """(K, W) data from the (N, W) codeword `v`, whose rows at the
        positions in `lost` are not read: f is interpolated through the
        first K surviving positions."""
        points = self.alphas + self.betas
        kept = [i for i in range(self.K + self.R) if i not in set(lost)]
        kept = kept[: self.K]
        block = lagrange_block(self.q, [points[i] for i in kept],
                               self.alphas)
        return combine(self.q, block, np.asarray(v, np.int64)[kept] % self.q)
