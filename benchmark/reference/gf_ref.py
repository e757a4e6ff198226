"""Plain GF(2^8) Reed-Solomon reference for the codec cells.

Written from jerasure's documented constructions (reed_sol.c ::
reed_sol_vandermonde_coding_matrix; cauchy.c ::
cauchy_original_coding_matrix, cauchy_improve_coding_matrix,
cauchy_good_general_coding_matrix; galois.c with w=8 and the primitive
polynomial 0x11d), with numpy table lookups for the byte arithmetic.
It imports nothing of the system under test: the coding matrix is built
here, from the profile's `technique`, and parity is computed byte by byte.

A reference module, named by a configuration's `reference` key, offers
`coding_matrix(profile)`, `apply(matrix, data)` and
`decode(coding, shards, want)`; a code this file does not build (SHEC,
CLAY) brings a module of its own.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

POLY = 0x11D  # jerasure's w=8 primitive polynomial (octal 0435)


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF(2^8) division by zero")
    if a == 0:
        return 0
    return int(EXP[(LOG[a] - LOG[b]) % 255])


@lru_cache(maxsize=1)
def mul_table() -> np.ndarray:
    """table[a, b] = a * b in GF(2^8)."""
    t = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            t[a, b] = EXP[LOG[a] + LOG[b]]
    return t


def n_ones(n: int) -> int:
    """Ones in the 8x8 GF(2) bitmatrix of multiply-by-n (cauchy_n_ones):
    column i of that matrix is n * 2^i."""
    return sum(bin(mul(n, 1 << i)).count("1") for i in range(8))


def cauchy_orig(k: int, m: int) -> np.ndarray:
    """m x k cauchy_orig coding matrix: 1 / (i ^ (m + j))."""
    return np.array([[div(1, i ^ (m + j)) for j in range(k)]
                     for i in range(m)], dtype=np.uint8)


def invert(mat: np.ndarray) -> np.ndarray:
    """Inverse of a square GF(2^8) matrix by Gauss-Jordan elimination."""
    n = mat.shape[0]
    a = [[int(v) for v in row] + [int(i == j) for j in range(n)]
         for i, row in enumerate(mat)]
    for c in range(n):
        piv = next(r for r in range(c, n) if a[r][c])
        a[c], a[piv] = a[piv], a[c]
        inv = div(1, a[c][c])
        a[c] = [mul(v, inv) for v in a[c]]
        for r in range(n):
            if r != c and a[r][c]:
                f = a[r][c]
                a[r] = [v ^ mul(f, w) for v, w in zip(a[r], a[c])]
    return np.array([row[n:] for row in a], dtype=np.uint8)


def decode(coding: np.ndarray, shards: dict[int, np.ndarray],
           want: list[int]) -> dict[int, np.ndarray]:
    """Shards `want` of a systematic code with this m x k coding matrix,
    from any k of the others (the first k by id)."""
    m, k = coding.shape
    gen = np.concatenate([np.eye(k, dtype=np.uint8), coding])
    use = sorted(shards)[:k]
    data = apply(invert(gen[use]), np.stack([shards[i] for i in use]))
    return {w: apply(gen[[w]], data)[0] for w in want}


def cauchy_good(k: int, m: int) -> np.ndarray:
    """m x k cauchy_good coding matrix (jerasure, w=8, the general path
    taken for m > 2)."""
    if m == 2:
        raise NotImplementedError("m=2 uses jerasure's cbest tables")
    mat = cauchy_orig(k, m).astype(int).tolist()
    # cauchy_improve_coding_matrix: scale each column so row 0 is ones
    for j in range(k):
        if mat[0][j] != 1:
            t = div(1, mat[0][j])
            for i in range(m):
                mat[i][j] = mul(mat[i][j], t)
    # then scale each later row by the inverse of the element that
    # leaves the fewest ones in its bitmatrix
    for i in range(1, m):
        best = sum(n_ones(v) for v in mat[i])
        best_j = -1
        for j in range(k):
            if mat[i][j] != 1:
                t = div(1, mat[i][j])
                tno = sum(n_ones(mul(v, t)) for v in mat[i])
                if tno < best:
                    best, best_j = tno, j
        if best_j != -1:
            t = div(1, mat[i][best_j])
            mat[i] = [mul(v, t) for v in mat[i]]
    return np.array(mat, dtype=np.uint8)


def reed_sol_van(k: int, m: int) -> np.ndarray:
    """m x k reed_sol_van coding matrix: jerasure's extended Vandermonde
    matrix of k + m rows, turned systematic by column operations
    (reed_sol_big_vandermonde_distribution_matrix), then row k made all
    ones by scaling columns and each later row's first element one by
    scaling rows; the last m rows."""
    rows, cols = k + m, k
    d = [[0] * cols for _ in range(rows)]
    d[0][0] = 1
    if rows > 1:
        d[rows - 1][cols - 1] = 1
    for i in range(1, rows - 1):
        v = 1
        for j in range(cols):
            d[i][j] = v
            v = mul(v, i)
    for i in range(1, cols):
        j = next((r for r in range(i, rows) if d[r][i]), None)
        if j is None:
            raise ValueError(f"no Vandermonde pivot for column {i}")
        if j != i:
            d[i], d[j] = d[j], d[i]
        if d[i][i] != 1:
            t = div(1, d[i][i])
            for r in range(rows):
                d[r][i] = mul(t, d[r][i])
        for j in range(cols):
            e = d[i][j]
            if j != i and e:
                for r in range(rows):
                    d[r][j] ^= mul(e, d[r][i])
    for j in range(cols):
        if d[cols][j] != 1:
            t = div(1, d[cols][j])
            for r in range(cols, rows):
                d[r][j] = mul(t, d[r][j])
    for i in range(cols + 1, rows):
        if d[i][0] != 1:
            t = div(1, d[i][0])
            d[i] = [mul(v, t) for v in d[i]]
    return np.array(d[k:], dtype=np.uint8)


#: jerasure's matrix techniques by profile name; jerasure's default first
TECHNIQUES = {"reed_sol_van": reed_sol_van, "cauchy_orig": cauchy_orig,
              "cauchy_good": cauchy_good}


def coding_matrix(profile: dict) -> np.ndarray:
    """The m x k coding matrix an erasure-code profile states."""
    technique = profile.get("technique", "reed_sol_van")
    if technique not in TECHNIQUES:
        raise NotImplementedError(f"technique {technique!r}: this reference "
                                  f"builds {sorted(TECHNIQUES)}")
    return TECHNIQUES[technique](int(profile["k"]), int(profile["m"]))


def apply(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """[rows, n] GF(2^8) matrix times [n, L] bytes -> [rows, L]."""
    rows, n = mat.shape
    table = mul_table()
    out = np.zeros((rows, data.shape[1]), dtype=np.uint8)
    for i in range(rows):
        acc = out[i]
        for j in range(n):
            c = int(mat[i, j])
            if c == 1:
                acc ^= data[j]
            elif c:
                acc ^= table[c][data[j]]
    return out
