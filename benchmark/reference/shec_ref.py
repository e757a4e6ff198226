"""Plain SHEC reference: the shingled code's coding matrix, its parity,
the least read set for lost chunks, and their rebuild, in numpy and
plain Python GF(2^8) arithmetic.

Written from Ceph's src/erasure-code/shec/ErasureCodeShec.cc
(`shec_reedsolomon_coding_matrix`, `shec_calc_recovery_efficiency1`)
and doc/rados/operations/erasure-code-shec.rst, after Miyamae et al.,
"Erasure Code with Shingled Local Parity Groups for Efficient Recovery
from Multiple Disk Failures" (HotDep 2014):

- The coefficients are jerasure's reed_sol_van matrix of k data and m
  parity chunks (gf_ref.reed_sol_van, w = 8, polynomial 0x11d).
- technique=single keeps one group of m parities, each of which covers
  a cyclic window of data chunks set by the durability estimator c.
  technique=multiple (the default) splits the parities into two groups
  (m1, c1) and (m2 = m - m1, c2 = c - c1), c1 from 0 to c/2 and m1 from
  0 to m, and keeps the split whose mean chunks read for one lost chunk
  (recovery_efficiency1) is least, the first such split on a tie.
- Parity row rr of a group (mg, cg) zeroes the data columns from
  ((rr + cg) k / mg) mod k up to, not including, (rr k / mg) mod k,
  cyclically.

The least read set and the rebuild need no plan: `minimum_read` tries
the survivors' subsets, smallest first, and keeps the first whose
generator rows span the lost chunks' rows; `rebuild` solves for the lost
chunks over a basis of the chunks it is given.

Departures from upstream: upstream checks k, m, c and w more strictly
(w 8, 16 or 32; bounds on k and m), and its decode picks its read set
by its own search, which may differ from the one chosen here where
several of the least size exist.  Upstream's source was not at hand:
this follows its construction as written above.  It imports nothing of
the system under test.
"""
from __future__ import annotations

import importlib
import importlib.util
import itertools
import os

import numpy as np


def _sibling(name: str):
    """A sibling reference module, whether this file was imported as
    `reference.shec_ref` or loaded by its path."""
    if __package__:
        return importlib.import_module(f"{__package__}.{name}")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"shec_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gf_ref = _sibling("gf_ref")


def recovery_efficiency1(k: int, m1: int, m2: int, c1: int,
                         c2: int) -> float:
    """shec_calc_recovery_efficiency1: the mean number of chunks read to
    rebuild one lost chunk, over the k + m chunks."""
    r_eff = [10 ** 8] * k
    total = 0
    for mg, cg in ((m1, c1), (m2, c2)):
        for rr in range(mg):
            first, last = rr * k // mg, (rr + cg) * k // mg
            width = last - first
            cc = first % k
            while True:
                r_eff[cc] = min(r_eff[cc], width)
                cc = (cc + 1) % k
                if cc == last % k:
                    break
            total += width
    return (total + sum(r_eff)) / (k + m1 + m2)


def split(k: int, m: int, c: int, technique: str) -> tuple[int, int]:
    """(m1, c1) of the two parity groups; (0, 0) is one group of m."""
    if technique == "single":
        return 0, 0
    if technique != "multiple":
        raise ValueError(f"technique {technique!r}: single or multiple")
    best, best_e = (0, 0), None
    for c1 in range(c // 2 + 1):
        for m1 in range(m + 1):
            m2, c2 = m - m1, c - c1
            if m1 < c1 or m2 < c2:
                continue
            if (m1 == 0) != (c1 == 0) or (m2 == 0) != (c2 == 0):
                continue
            e = recovery_efficiency1(k, m1, m2, c1, c2)
            if best_e is None or e < best_e - 1e-12:
                best, best_e = (m1, c1), e
    return best


def coding_matrix(profile: dict) -> np.ndarray:
    """The m x k coding matrix a `plugin=shec` profile states."""
    k, m, c = int(profile["k"]), int(profile["m"]), int(profile["c"])
    m1, c1 = split(k, m, c, profile.get("technique", "multiple"))
    mat = gf_ref.reed_sol_van(k, m).copy()
    for first, mg, cg in ((0, m1, c1), (m1, m - m1, c - c1)):
        for rr in range(mg):
            end = rr * k // mg % k
            cc = (rr + cg) * k // mg % k
            while cc != end:
                mat[first + rr, cc] = 0
                cc = (cc + 1) % k
    return mat


def apply(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """[rows, n] GF(2^8) matrix times [n, L] bytes -> [rows, L]."""
    return gf_ref.apply(mat, data)


def generator(coding: np.ndarray) -> list[list[int]]:
    """The k + m generator rows [I; coding], as lists of ints."""
    m, k = coding.shape
    return ([[int(i == j) for j in range(k)] for i in range(k)]
            + [[int(v) for v in row] for row in coding])


def _echelon(rows: list[list[int]]) -> list[list[int]]:
    """Row echelon form by Gaussian elimination; zero rows dropped."""
    a = [list(r) for r in rows]
    out = []
    cols = len(a[0]) if a else 0
    for col in range(cols):
        piv = next((r for r in a if r[col]), None)
        if piv is None:
            continue
        a.remove(piv)
        inv = gf_ref.div(1, piv[col])
        piv = [gf_ref.mul(v, inv) for v in piv]
        a = [[v ^ gf_ref.mul(r[col], w) for v, w in zip(r, piv)]
             if r[col] else r for r in a]
        out.append(piv)
    return out


def rank(rows: list[list[int]]) -> int:
    return len(_echelon(rows))


def minimum_read(lost, k: int, m: int, coding: np.ndarray) -> tuple:
    """The fewest surviving chunks from which every chunk of `lost` can
    be computed: the first subset of the survivors, smallest first and
    in lexicographic order, whose generator rows span the lost rows."""
    gen = generator(coding)
    lost = sorted(set(lost))
    survivors = [i for i in range(k + m) if i not in lost]
    want = [gen[i] for i in lost]
    for size in range(1, len(survivors) + 1):
        for subset in itertools.combinations(survivors, size):
            rows = [gen[i] for i in subset]
            if rank(rows) == rank(rows + want):
                return subset
    raise ValueError(f"chunks {lost} cannot be rebuilt")


def rebuild(lost, shards: dict, coding: np.ndarray) -> dict:
    """The chunks `lost` from the chunks `shards` (id -> bytes): each
    lost generator row written as a combination of a basis of the given
    chunks' rows (a GF solve), and that combination of their bytes."""
    gen = generator(coding)
    basis: list[int] = []
    for i in sorted(shards):
        if rank([gen[j] for j in basis + [i]]) > len(basis):
            basis.append(i)
    r = len(basis)
    out = {}
    for w in sorted(set(lost)):
        # solve sum_b x_b gen[b] = gen[w]: the k x (r + 1) system with
        # the basis rows as columns, augmented by gen[w]
        aug = [[gen[b][col] for b in basis] + [gen[w][col]]
               for col in range(len(gen[w]))]
        ech = _echelon(aug)
        if any(not any(row[:r]) for row in ech):
            raise ValueError(f"chunk {w} is not in the span of "
                             f"{sorted(shards)}")
        # back substitution over the reduced rows (pivot columns 1)
        x = [0] * r
        for row in reversed(ech):
            p = next(i for i in range(r) if row[i])
            acc = row[r]
            for j in range(p + 1, r):
                acc ^= gf_ref.mul(row[j], x[j])
            x[p] = acc
        coef = np.array([x], dtype=np.uint8)
        out[w] = gf_ref.apply(coef, np.stack(
            [np.asarray(shards[b], dtype=np.uint8) for b in basis]))[0]
    return out
