"""Plain Clay code reference: encode and single-shard repair, plane by
plane and byte by byte in numpy, as Vajha et al., "Clay Codes: Moulding
MDS Codes to Yield an MSR Code" (USENIX FAST 2018) give them.

Geometry: q = d - k + 1, t = (k + m) / q, Z = q^t planes a chunk.  Node
(x, y), x < q, y < t, is shard y*q + x; plane z has the base-q digits
z_y, y = 0 the least significant.  In each plane the uncoupled symbols U
of the k + m nodes are a codeword of the scalar MDS code [I; C], with C
from gf_ref.coding_matrix(profile).  The stored (coupled) symbols pair
up: for x != z_y, node (x, y) in plane z and its partner (z_y, y) in
plane z' (z with digit y set to x) hold C1 = U1 ^ g*U2 and C2 = g*U1 ^
U2, g = 2 over GF(2^8) with polynomial 0x11d; for x == z_y, C = U.

- Encode is the paper's layered decode with the m parity nodes erased:
  planes in increasing intersection-score order (the number of erased
  nodes that are vertices, x == z_y, of the plane), each plane's U of
  the surviving nodes from their pairs (or, where the partner is erased,
  from the partner's U of a plane already done), the plane's erased U
  from the MDS code, then the erased nodes' C from their pairs.
- Repair of node (x0, y0) follows the paper's repair algorithm: every
  other node sends the Z/q planes with z_y0 = x0.  In each such plane the
  U of the nodes outside column y0 come from their pairs, all of which
  lie in those planes; the MDS code gives the U of column y0; the lost
  node is a vertex there (C = U), and each other node (x, y0) of the
  column gives, from its C and U, the lost node's C in plane z with
  digit y0 set to x.  No folded repair matrix is used.

A chunk is held as [Z, W] bytes, one row a plane: W bytes of every
stripe side by side, each column its own codeword.

Departure from upstream: ErasureCodeClay builds its pairwise transform
from a (2,2) jerasure code, so this parity is not claimed byte-identical
with upstream's (the upstream source was not at hand).  It imports
nothing of the system under test.
"""
from __future__ import annotations

import importlib
import importlib.util
import os

import numpy as np

GAMMA = 2


def _sibling(name: str):
    """A sibling reference module, whether this file was imported as
    `reference.clay_ref` or loaded by its path."""
    if __package__:
        return importlib.import_module(f"{__package__}.{name}")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"clay_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gf_ref = _sibling("gf_ref")


def coding_matrix(profile: dict) -> np.ndarray:
    """The inner MDS code's m x k coding matrix C."""
    return gf_ref.coding_matrix(profile)


def geometry(profile: dict) -> tuple[int, int, int, int, int, int]:
    """(k, m, d, q, t, Z) of a profile."""
    k, m = int(profile["k"]), int(profile["m"])
    d = int(profile.get("d", k + m - 1))
    q = d - k + 1
    if (k + m) % q:
        raise ValueError(f"q = {q} does not divide k + m = {k + m}")
    t = (k + m) // q
    return k, m, d, q, t, q ** t


def repair_planes(profile: dict, lost: int) -> list[int]:
    """The planes every helper sends to repair `lost`: z_y0 = x0."""
    _, _, _, q, _, Z = geometry(profile)
    x0, y0 = lost % q, lost // q
    return [z for z in range(Z) if _digit(z, y0, q) == x0]


def _digit(z: int, y: int, q: int) -> int:
    return (z // q ** y) % q


def _with_digit(z: int, y: int, x: int, q: int) -> int:
    return z + (x - _digit(z, y, q)) * q ** y


def _mul(c: int, v: np.ndarray) -> np.ndarray:
    return gf_ref.mul_table()[c][v]


def _generator(profile: dict) -> np.ndarray:
    k = int(profile["k"])
    return np.concatenate([np.eye(k, dtype=np.uint8), coding_matrix(profile)])


INV_DET = gf_ref.div(1, 1 ^ gf_ref.mul(GAMMA, GAMMA))  # 1 / (1 + g^2)
INV_G = gf_ref.div(1, GAMMA)


def _uncouple(c1: np.ndarray, c2: np.ndarray) -> np.ndarray:
    """U1 from C1 = U1 ^ g*U2 and C2 = g*U1 ^ U2."""
    return _mul(INV_DET, c1 ^ _mul(GAMMA, c2))


def encode(profile: dict, data: np.ndarray) -> np.ndarray:
    """[k, Z, W] data chunks -> [m, Z, W] parity chunks."""
    k, m, _, q, t, Z = geometry(profile)
    n = k + m
    W = data.shape[2]
    gen = _generator(profile)
    erased = set(range(k, n))
    survivors = list(range(k))
    inv = gf_ref.invert(gen[survivors])
    C = np.zeros((n, Z, W), dtype=np.uint8)
    C[:k] = data
    U = np.zeros((n, Z, W), dtype=np.uint8)

    def score(z):
        return sum(y * q + _digit(z, y, q) in erased for y in range(t))

    for z in sorted(range(Z), key=lambda z: (score(z), z)):
        for node in survivors:
            x, y = node % q, node // q
            zy = _digit(z, y, q)
            if zy == x:
                U[node, z] = C[node, z]
                continue
            partner, zp = y * q + zy, _with_digit(z, y, x, q)
            if partner in erased:
                # the partner's U in zp, a plane of a lower score
                U[node, z] = C[node, z] ^ _mul(GAMMA, U[partner, zp])
            else:
                U[node, z] = _uncouple(C[node, z], C[partner, zp])
        base = gf_ref.apply(inv, U[survivors, z])
        for e in sorted(erased):
            U[e, z] = gf_ref.apply(gen[[e]], base)[0]
    for e in sorted(erased):
        x, y = e % q, e // q
        for z in range(Z):
            zy = _digit(z, y, q)
            if zy == x:
                C[e, z] = U[e, z]
            else:
                C[e, z] = U[e, z] ^ _mul(GAMMA, U[y * q + zy,
                                                  _with_digit(z, y, x, q)])
    return C[k:]


def repair(profile: dict, lost: int,
           helpers: dict[int, np.ndarray]) -> np.ndarray:
    """The lost node's [Z, W] chunk from every other node's repair
    planes: helpers[node] is [Z/q, W], its repair_planes in order."""
    k, m, _, q, _, Z = geometry(profile)
    n = k + m
    x0, y0 = lost % q, lost // q
    planes = repair_planes(profile, lost)
    pos = {z: i for i, z in enumerate(planes)}
    W = next(iter(helpers.values())).shape[1]
    gen = _generator(profile)
    outside = [i for i in range(n) if i // q != y0][:k]
    inv = gf_ref.invert(gen[outside])
    column = [y0 * q + x for x in range(q)]
    col_rows = gen[column]
    out = np.zeros((Z, W), dtype=np.uint8)
    for z in planes:
        U = np.zeros((k, W), dtype=np.uint8)
        for i, node in enumerate(outside):
            x, y = node % q, node // q
            zy = _digit(z, y, q)
            c1 = helpers[node][pos[z]]
            if zy == x:
                U[i] = c1
            else:
                partner, zp = y * q + zy, _with_digit(z, y, x, q)
                U[i] = _uncouple(c1, helpers[partner][pos[zp]])
        u_col = gf_ref.apply(col_rows, gf_ref.apply(inv, U))
        out[z] = u_col[x0]  # the lost node is a vertex of z
        for x in range(q):
            if x == x0:
                continue
            # node (x, y0) in z pairs with the lost node in zp:
            # C(node; z) = U(node; z) ^ g*U(lost; zp) and
            # C(lost; zp) = U(lost; zp) ^ g*U(node; z)
            zp = _with_digit(z, y0, x, q)
            u_lost = _mul(INV_G, helpers[y0 * q + x][pos[z]] ^ u_col[x])
            out[zp] = u_lost ^ _mul(GAMMA, u_col[x])
    return out
