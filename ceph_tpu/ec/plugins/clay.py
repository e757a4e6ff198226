"""CLAY — coupled-layer MSR regenerating code plugin (k, m, d).

Reference: src/erasure-code/clay/ErasureCodeClay.{h,cc} — repair of a single
lost chunk reads only sub-chunks from d helper chunks (bandwidth-optimal MSR
point), introducing get_sub_chunk_count() and sub-chunk-range
minimum_to_decode into the codec interface (SURVEY.md §2.1).

Construction (Clay codes, FAST'18, as the reference implements):
- q = d - k + 1, t = (k+m)/q; node (x, y) for x in [0,q), y in [0,t);
  chunk index n = y*q + x; data nodes are n < k.
- Each chunk holds q^t sub-chunks, one per "plane" z, whose base-q digits
  are (z_0..z_{t-1}) (y=0 least significant here).
- Uncoupled symbols U(x,y;z) form, per plane, a codeword of the scalar MDS
  code [I_k; C] (the same jerasure-exact RS generator as the rs plugin).
- Coupling: for x != z_y, the pair P1=(x,y;z), P2=(z_y,y;z') with
  z' = z(y -> x) satisfies C1 = U1 ^ g*U2 and C2 = g*U1 ^ U2 (g = 2;
  det 1^g^2 = (1+g)^2 != 0); for x == z_y ("vertex"), C = U.
- Encode and multi-erasure decode run the layered algorithm: planes in
  increasing intersection-score order, U recovered via pair inversion or
  earlier planes, per-plane MDS decode of erased U, then C of erased nodes
  from U pairs.
- Single-chunk repair with d = k+m-1 (the reference's default d) reads only
  the q^(t-1) planes with z_{y0} = x0 from every survivor — bandwidth
  d/(k*q) of naive (BASELINE.json config 4 measures exactly this).

Profile: `k`, `m`, `d`, `scalar_mds` and `technique`, as upstream's.
The inner scalar MDS code is jerasure's (`scalar_mds=jerasure`, the only
one built here), its coding matrix from the RS plugin's own
`build_coding_matrix(technique, k, m)`; `technique` defaults to
reed_sol_van, as upstream.

Repair through `decode` (upstream's `is_repair` branch of
ErasureCodeClay::decode, looped over stripes as ECUtil::decode does):
each helper buffer may hold only that helper's repair-plane sub-chunks,
`chunk_size / q` bytes a stripe, stripe after stripe; any number of
stripes is rebuilt in one apply of the cached [Z, d*Z/q] repair matrix.
The helpers' stripe-major `[stripe, plane, sub_len]` bytes are laid out
on the device as the apply's plane-major rows (`clay.gather`), and its
output as stripe-major chunk bytes before the fetch (`clay.scatter`):
on the v5e host a numpy transpose of those 88 MiB took ~100 ms, the
device relayout a few.

Scope notes vs the reference: d must satisfy q | (k+m) (the reference pads
with shortened virtual nodes otherwise); bandwidth-optimal repair is
implemented for d = k+m-1 with all survivors as helpers, and falls back to
full decode for other cases.  The layout the OSD writes is one stripe an
object (`ec_backend._ec_encode`); upstream's 4 KiB stripe unit, with 64 B
sub-chunks and a codeword a stripe, is not laid out here.  Parity bytes
are internally defined (empty reference mount, SURVEY.md §0): upstream
builds its pairwise transform from a (2,2) jerasure code, so parity is
not claimed byte-identical with upstream; sub-chunk accounting and
repair-bandwidth semantics are what tests pin.
"""
from __future__ import annotations

import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...common.host_heap import retain_freed_large_blocks
from ...common.kernel_telemetry import TELEMETRY
from ...common.tracer import stage
from ...gf.matrix import decode_matrix_for, systematic_generator
from ...gf.tables import GF_MUL_TABLE, gf_inv
from ..interface import ErasureCode, InsufficientChunks, InvalidProfile
from ..registry import ErasureCodePlugin
from .rs import _to_host, build_coding_matrix

#: inner scalar MDS code families upstream's `scalar_mds` names that are
#: built here
SCALAR_MDS = ("jerasure",)

GAMMA = 2
_INV_DET = gf_inv(1 ^ GF_MUL_TABLE[GAMMA, GAMMA])  # 1/(1 + g^2)
_INV_G = gf_inv(GAMMA)


def _gmul(c: int, arr: np.ndarray) -> np.ndarray:
    return GF_MUL_TABLE[c, arr]


class ClayCodec(ErasureCode):
    def __init__(self, profile: dict | None = None):
        # the repair's host fetch lands in recycled heap pages, not newly
        # mapped ones (the same policy the RS codec applies)
        retain_freed_large_blocks()
        #: (lost, helpers) -> (repair matrix, stable digest)
        self._repair_mat_cache: dict[tuple, tuple[np.ndarray, str]] = {}
        self._count_lock = threading.Lock()
        #: repair-matrix cache lookups, and the repair's helper bytes in
        #: and rebuilt bytes out
        self.repair_matrix_hits = 0
        self.repair_matrix_misses = 0
        self.repair_bytes_in = 0
        self.repair_bytes_out = 0
        super().__init__(profile)

    def init(self, profile: dict) -> None:
        self._repair_mat_cache.clear()  # re-init invalidates geometry
        self.profile = dict(profile)
        self.k = self.parse_int(profile, "k", 4)
        self.m = self.parse_int(profile, "m", 2)
        self.d = self.parse_int(profile, "d", self.k + self.m - 1)
        if not (self.k <= self.d <= self.k + self.m - 1):
            raise InvalidProfile(
                f"CLAY requires k <= d <= k+m-1, got k={self.k} m={self.m} d={self.d}"
            )
        self.q = self.d - self.k + 1
        n = self.k + self.m
        if n % self.q:
            raise InvalidProfile(
                f"(k+m)={n} must be divisible by q=d-k+1={self.q} "
                "(the reference pads with shortened nodes; unsupported here)"
            )
        self.t = n // self.q
        self.sub_chunk_count = self.q**self.t
        self.scalar_mds = profile.get("scalar_mds", "jerasure")
        if self.scalar_mds not in SCALAR_MDS:
            raise InvalidProfile(
                f"scalar_mds={self.scalar_mds!r} unsupported; known: "
                f"{SCALAR_MDS}")
        self.technique = profile.get("technique", "reed_sol_van")
        self.coding = build_coding_matrix(self.technique, self.k, self.m)
        self.generator = systematic_generator(self.coding)

    def get_sub_chunk_count(self) -> int:
        return self.sub_chunk_count

    def get_chunk_size(self, stripe_width: int) -> int:
        base = super().get_chunk_size(stripe_width)
        # chunk must split into q^t sub-chunks of CHUNK_ALIGN-friendly size
        unit = self.sub_chunk_count
        return -(-base // unit) * unit

    # -- geometry ---------------------------------------------------------
    def _node(self, n: int) -> tuple[int, int]:
        return n % self.q, n // self.q

    def _digit(self, z: int, y: int) -> int:
        return (z // self.q**y) % self.q

    def _replace(self, z: int, y: int, x: int) -> int:
        p = self.q**y
        return z - self._digit(z, y) * p + x * p

    # -- layered decode (ErasureCodeClay::decode_layered) -----------------
    def _layered_decode(
        self, C: dict[int, np.ndarray], erased: list[int], sub_len: int
    ) -> dict[int, np.ndarray]:
        """C: node -> [Z, sub_len] known coupled chunks; returns C for erased.

        TPU-native restructuring of ErasureCodeClay::decode_layered: planes
        are grouped by intersection score (all cross-plane dependencies
        point at strictly lower scores), couplings are vectorized numpy
        over each group, and the per-plane MDS decodes collapse into ONE
        on-device bitplane matmul per score group instead of Z host
        apply_matrix calls."""
        nq, t, Z = self.q, self.t, self.sub_chunk_count
        n_nodes = self.k + self.m
        erased_set = set(erased)
        if len(erased_set) > self.m:
            raise InsufficientChunks(f"{len(erased_set)} erasures > m={self.m}")
        from ...ops.bitplane import apply_matrix_jax

        U = np.zeros((n_nodes, Z, sub_len), dtype=np.uint8)
        Cd = np.zeros((n_nodes, Z, sub_len), dtype=np.uint8)
        for node, v in C.items():
            Cd[node] = v
        zs_all = np.arange(Z)
        digits = np.stack(
            [(zs_all // nq**y) % nq for y in range(t)]
        )  # [t, Z]
        scores = np.zeros(Z, dtype=np.int64)
        for y in range(t):
            scores += np.isin(y * nq + digits[y], list(erased_set))
        avail_nodes = sorted(set(range(n_nodes)) - erased_set)
        dm = decode_matrix_for(self.generator, self.k, avail_nodes).astype(np.uint8)
        parity_erased = bool(erased_set & set(range(self.k, n_nodes)))
        for s in range(int(scores.max()) + 1):
            zs = zs_all[scores == s]
            if zs.size == 0:
                continue
            # uncoupled U for available nodes, vectorized over the group
            for node in avail_nodes:
                x, y = self._node(node)
                digs = digits[y, zs]                      # [nZ]
                pnode = y * nq + digs
                zp = zs + (x - digs) * nq**y
                vertex = (digs == x)[:, None]
                partner_ok = (~np.isin(pnode, list(erased_set)))[:, None]
                c1 = Cd[node, zs]
                c2 = Cd[pnode, zp]
                u_pair = _gmul(_INV_DET, c1 ^ _gmul(GAMMA, c2))
                u_part = c1 ^ _gmul(GAMMA, U[pnode, zp])  # zp has score s-1
                U[node, zs] = np.where(
                    vertex, c1, np.where(partner_ok, u_pair, u_part)
                )
            # one batched MDS decode for every plane in the group
            sub = U[avail_nodes[: self.k]][:, zs].reshape(self.k, -1)
            data_u = np.asarray(apply_matrix_jax(dm, sub))
            full = np.zeros((n_nodes, zs.size * sub_len), dtype=np.uint8)
            full[: self.k] = data_u
            if parity_erased:
                full[self.k :] = np.asarray(
                    apply_matrix_jax(self.coding, data_u)
                )
            for node in erased_set:
                U[node, zs] = full[node].reshape(zs.size, sub_len)
        # rebuild coupled C for erased nodes from the complete U
        out: dict[int, np.ndarray] = {}
        for node in erased:
            x, y = self._node(node)
            digs = digits[y]
            pnode = y * nq + digs
            zp = zs_all + (x - digs) * nq**y
            vertex = (digs == x)[:, None]
            out[node] = np.where(
                vertex, U[node], U[node] ^ _gmul(GAMMA, U[pnode, zp])
            )
        return out

    # -- interface --------------------------------------------------------
    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        data_chunks = np.ascontiguousarray(data_chunks, dtype=np.uint8)
        k, L = data_chunks.shape
        assert k == self.k
        Z = self.sub_chunk_count
        if L % Z:
            raise ValueError(f"chunk length {L} not divisible by {Z} sub-chunks")
        sub_len = L // Z
        C = {i: data_chunks[i].reshape(Z, sub_len) for i in range(self.k)}
        parity = self._layered_decode(
            C, list(range(self.k, self.k + self.m)), sub_len
        )
        return np.stack(
            [parity[self.k + i].reshape(L) for i in range(self.m)]
        )

    def decode(self, want_to_read, chunks, chunk_size):
        """One lost shard with d = k+m-1 helpers is repaired (upstream's
        `is_repair` branch), from whole chunks or from the helpers'
        repair-plane sub-chunks alone; anything else decodes whole chunks.

        `chunk_size` is one stripe's chunk.  A buffer of sub-chunks holds
        `chunk_size / q` bytes a stripe, for any number of stripes, stripe
        after stripe: the ranges `minimum_to_decode` returns, read once a
        stripe.  The rebuilt shard comes back stripe after stripe.  A
        buffer of exactly `chunk_size` bytes is read as a whole chunk, so
        q stripes of sub-chunks read as one whole chunk: give them in two
        calls."""
        want = set(want_to_read)
        missing = want - set(chunks)
        stripes = self._repair_stripes(missing, chunks, chunk_size)
        if stripes is None:
            return super().decode(want, chunks, chunk_size)
        (lost,) = missing
        out = {w: np.asarray(chunks[w], dtype=np.uint8) for w in want - missing}
        out[lost] = self._repair(lost, chunks, chunk_size, stripes)
        return out

    def _repair_stripes(self, missing, chunks, chunk_size) -> int | None:
        """Stripes of repair-plane sub-chunks in each helper buffer where
        `decode` can repair from them, else None."""
        Z = self.sub_chunk_count
        if (len(missing) != 1 or self.d != self.k + self.m - 1
                or len(chunks) < self.d or chunk_size <= 0
                or chunk_size % Z):
            return None
        lengths = {len(v) for v in chunks.values()}
        if len(lengths) != 1:
            return None
        (length,) = lengths
        part = chunk_size // self.q
        if length in (0, chunk_size) or length % part:
            return None  # whole chunks go through decode_chunks
        return length // part

    def decode_chunks(self, want_to_read, chunks):
        have = {int(i): np.asarray(v, dtype=np.uint8) for i, v in chunks.items()}
        L = len(next(iter(have.values())))
        Z = self.sub_chunk_count
        sub_len = L // Z
        erased = sorted(set(range(self.k + self.m)) - set(have))
        lost_wanted = sorted(set(want_to_read) - set(have))
        if not lost_wanted:
            return {w: have[w] for w in want_to_read}
        if len(erased) == 1 and self.d == self.k + self.m - 1 and len(have) >= self.d:
            out = {erased[0]: self._repair(erased[0], have, L, 1)}
        else:
            C = {i: v.reshape(Z, sub_len) for i, v in have.items()}
            dec = self._layered_decode(C, erased, sub_len)
            out = {n: v.reshape(Z * sub_len) for n, v in dec.items()}
        result = {}
        for w in set(want_to_read):
            result[w] = have[w] if w in have else out[w]
        return result

    # -- bandwidth-optimal single repair (d = k+m-1) ----------------------
    def repair_planes(self, lost: int) -> list[int]:
        """Planes read during repair of `lost`: z with z_{y0} == x0."""
        x0, y0 = self._node(lost)
        return [
            z for z in range(self.sub_chunk_count) if self._digit(z, y0) == x0
        ]

    def repair_subchunk_ranges(self, lost: int) -> list[tuple[int, int]]:
        """Contiguous (offset, count) runs of sub-chunk indices helpers read
        (the shape minimum_to_decode reports, reference:
        ErasureCodeClay::minimum_to_decode sub-chunk ranges)."""
        planes = self.repair_planes(lost)
        runs: list[tuple[int, int]] = []
        for z in planes:
            if runs and runs[-1][0] + runs[-1][1] == z:
                runs[-1] = (runs[-1][0], runs[-1][1] + 1)
            else:
                runs.append((z, 1))
        return runs

    def minimum_to_decode(self, want_to_read, available):
        want = set(want_to_read)
        avail = set(available)
        if want <= avail:
            return {c: [(0, -1)] for c in sorted(want)}
        missing = want - avail
        if (
            len(missing) == 1
            and self.d == self.k + self.m - 1
            and len(avail) >= self.d
        ):
            ranges = self.repair_subchunk_ranges(next(iter(missing)))
            plan = {c: list(ranges) for c in sorted(avail)[: self.d]}
            # wanted-and-available chunks are read in full, not just the
            # repair planes a helper contributes (reference: Clay's
            # minimum_to_decode merges want_to_read into the helper set)
            for c in want & avail:
                plan[c] = [(0, -1)]
            return plan
        if len(avail) < self.k:
            raise InsufficientChunks(f"need {self.k} chunks, have {len(avail)}")
        return {c: [(0, -1)] for c in sorted(avail)[: self.k]}

    def repair_matrix(self, lost: int, helpers: tuple[int, ...]) -> np.ndarray:
        """[Z, len(helpers)*nB] GF(2^8) matrix M with
        ``lost_subchunks = M @ fetched``, where `fetched` stacks each
        helper's repair-plane sub-chunk rows in helper order.

        The ENTIRE single-shard repair — pair uncoupling, per-plane MDS
        decode, parity re-encode, recoupling — is GF-linear in the fetched
        bytes, so it collapses into one cached matrix and repair becomes a
        single on-device bitplane/Pallas apply.  (TPU-first restructure of
        ErasureCodeClay::repair's layered host loop; the algebra below IS
        the layered algorithm, run symbolically on coefficient rows
        instead of chunk bytes.)"""
        return self.repair_matrix_entry(lost, helpers)[0]

    def repair_matrix_entry(self, lost: int,
                            helpers: tuple[int, ...]) -> tuple:
        """(repair matrix, its stable digest) — the digest is computed
        once at cache fill and keys the device bitmatrix cache, so the
        recovery path's repeated repair applies stop paying a fresh
        ``M.tobytes()`` host copy per rebuilt chunk (cephdma)."""
        key = (lost, helpers)
        cached = self._repair_mat_cache.get(key)
        with self._count_lock:
            if cached is None:
                self.repair_matrix_misses += 1
            else:
                self.repair_matrix_hits += 1
        TELEMETRY.record_repair_matrix(hit=cached is not None)
        if cached is not None:
            return cached
        from ...ops.bitplane import matrix_digest

        with stage("clay.repair_matrix"):
            M = self._build_repair_matrix(lost, helpers)
            ent = (M, matrix_digest(M))
        self._repair_mat_cache[key] = ent
        return ent

    def _build_repair_matrix(self, lost: int,
                             helpers: tuple[int, ...]) -> np.ndarray:
        from ...gf.reference_codec import apply_matrix as gf_apply

        nq, Z = self.q, self.sub_chunk_count
        n_nodes = self.k + self.m
        x0, y0 = self._node(lost)
        planes = np.asarray(self.repair_planes(lost))
        nB = planes.size
        plane_pos = np.full(Z, -1, dtype=np.int64)
        plane_pos[planes] = np.arange(nB)
        n_in = len(helpers) * nB
        # coefficient rows: Cb[node, b] = unit vector of input position
        # (helper node, repair plane b)
        Cb = np.zeros((n_nodes, nB, n_in), dtype=np.uint8)
        for hi, node in enumerate(helpers):
            Cb[node, np.arange(nB), hi * nB + np.arange(nB)] = 1
        U = np.zeros((n_nodes, nB, n_in), dtype=np.uint8)
        known_u_nodes = []
        for node in helpers:
            x, y = self._node(node)
            if y == y0:
                continue  # column y0 survivors: U unknown in B planes
            known_u_nodes.append(node)
            digs = (planes // nq**y) % nq                  # [nB]
            pnode = y * nq + digs
            zp = planes + (x - digs) * nq**y               # stays in B
            vertex = (digs == x)[:, None]
            c1 = Cb[node]
            c2 = Cb[pnode, plane_pos[zp]]
            U[node] = np.where(
                vertex, c1, _gmul(_INV_DET, c1 ^ _gmul(GAMMA, c2))
            )
        # batched MDS decode: unknown U's are exactly column y0 (q nodes);
        # survivors outside column y0 must supply at least k known U's
        unknown = [y0 * nq + x for x in range(nq)]
        if len(known_u_nodes) < self.k:
            raise InsufficientChunks(
                f"repair needs {self.k} helpers outside column {y0}, "
                f"have {len(known_u_nodes)}"
            )
        dm = decode_matrix_for(
            self.generator, self.k, known_u_nodes
        ).astype(np.uint8)
        sub = U[known_u_nodes[: self.k]].reshape(self.k, -1)
        data_u = gf_apply(dm, sub)
        full = np.zeros((n_nodes, nB * n_in), dtype=np.uint8)
        full[: self.k] = data_u
        full[self.k :] = gf_apply(self.coding, data_u)
        for node in unknown:
            U[node] = full[node].reshape(nB, n_in)
        # rebuild lost chunk: B-planes are vertex (C = U); others via pairs
        zs_all = np.arange(Z)
        dy0 = (zs_all // nq**y0) % nq
        pnode = y0 * nq + dy0                              # [Z]
        zp = zs_all + (x0 - dy0) * nq**y0                  # in B
        zpi = plane_pos[zp]
        u2 = U[pnode, zpi]                                 # [Z, n_in]
        # C2 = g*U1 ^ U2 with P1=(lost;z), P2=(pnode;zp):
        u1 = _gmul(_INV_G, Cb[pnode, zpi] ^ u2)
        M = np.where(
            (dy0 == x0)[:, None], U[lost, zpi], u1 ^ _gmul(GAMMA, u2)
        )
        return M

    def gather_repair_input(
        self, have: dict[int, np.ndarray], lost: int, sub_len: int,
        helpers: tuple[int, ...],
    ) -> np.ndarray:
        """[len(helpers)*nB, sub_len] — each helper's repair-plane
        sub-chunks of one stripe stacked in helper order (the layout
        repair_matrix contracts over); a buffer holds the whole chunk or
        only its repair planes."""
        planes = np.asarray(self.repair_planes(lost))
        rows = [np.asarray(have[n], dtype=np.uint8).reshape(-1, sub_len)
                for n in helpers]
        return np.concatenate([r[planes] if r.shape[0] == self.sub_chunk_count
                               else r for r in rows])

    def _repair(self, lost: int, have: dict[int, np.ndarray],
                chunk_size: int, stripes: int) -> np.ndarray:
        """Rebuild `lost`, `stripes` stripes of it, from the repair planes
        of all survivors: one cached-matrix device apply over every
        stripe's columns.  Several stripes are laid out on the device,
        around the apply: the helpers' stripe-major rows as its
        plane-major input, its output as stripe-major chunk bytes."""
        from ...common.device_policy import get_device_policy
        from ...ops.bitplane import apply_matrix_jax

        sub_len = chunk_size // self.sub_chunk_count
        # the relayout kernels run in interpret mode off the chip
        interpret = get_device_policy().backend() != "tpu"
        with stage("clay.repair"):
            helpers = tuple(sorted(have))
            M, m_key = self.repair_matrix_entry(lost, helpers)
            with stage("clay.gather"):
                if stripes == 1:
                    # one stripe's rows are plane-major once stacked, and
                    # the host stack compiles nothing for each new size
                    x = self.gather_repair_input(have, lost, sub_len, helpers)
                    with stage("gf.h2d"):
                        x = jax.device_put(x)
                else:
                    with stage("gf.h2d"):
                        rows = jax.device_put(
                            [np.asarray(have[h], dtype=np.uint8).reshape(
                                -1, sub_len) for h in helpers])
                    x = _plane_major(tuple(rows), stripes, interpret)
            out = apply_matrix_jax(M, x, mat_key=m_key)
            if stripes > 1:
                with stage("clay.scatter"):
                    out = _stripe_major(out, stripes, interpret)
            chunk = _to_host(out).reshape(stripes * chunk_size)
        with self._count_lock:
            self.repair_bytes_in += sum(
                int(np.asarray(v).nbytes) for v in have.values())
            self.repair_bytes_out += chunk.nbytes
        return chunk


# The device relayouts are block copies.  XLA lowers slices and
# concatenates to one device op a (helper, stripe) block, 768 a call at
# the Clay cell's size, and a reshape that splits or merges the minor
# dimension of a uint8 device array compiles for minutes on v5e
# (ops/pallas_gf.py).  So where a block is a whole number of uint8 tiles
# (8 rows, 128 bytes) each relayout is one Pallas kernel with a grid step
# a stripe; other shapes keep the slices.
def _tiles(nrows: int, sub_len: int) -> bool:
    return nrows % 8 == 0 and sub_len % 128 == 0


@partial(jax.jit, static_argnums=(1, 2))
def _plane_major(rows, stripes: int, interpret: bool = False):
    """Each helper's stripe-major [stripes*nB, sub_len] rows -> the
    apply's [helpers*nB, stripes*sub_len] input: row (helper, plane)
    holds that plane's sub-chunk of every stripe, stripe after stripe."""
    nB, sub_len = rows[0].shape[0] // stripes, rows[0].shape[1]
    if not _tiles(nB, sub_len):
        return jnp.concatenate([
            jnp.concatenate([r[s * nB:(s + 1) * nB] for s in range(stripes)],
                            axis=1)
            for r in rows])
    from jax.experimental import pallas as pl

    def copy(*refs):
        for h, r in enumerate(refs[:-1]):
            refs[-1][h * nB:(h + 1) * nB, :] = r[...]

    return pl.pallas_call(
        copy, grid=(stripes,),
        in_specs=[pl.BlockSpec((nB, sub_len), lambda s: (s, 0))
                  for _ in rows],
        out_specs=pl.BlockSpec((len(rows) * nB, sub_len),
                               lambda s: (0, s)),
        out_shape=jax.ShapeDtypeStruct(
            (len(rows) * nB, stripes * sub_len), jnp.uint8),
        interpret=interpret)(*rows)


@partial(jax.jit, static_argnums=(1, 2))
def _stripe_major(out, stripes: int, interpret: bool = False):
    """The apply's [Z, stripes*sub_len] output -> [stripes*Z, sub_len]:
    stripe after stripe, each stripe's chunk bytes."""
    Z, sub_len = out.shape[0], out.shape[1] // stripes
    if not _tiles(Z, sub_len):
        return jnp.concatenate([out[:, s * sub_len:(s + 1) * sub_len]
                                for s in range(stripes)])
    from jax.experimental import pallas as pl

    def copy(x, o):
        o[...] = x[...]

    return pl.pallas_call(
        copy, grid=(stripes,),
        in_specs=[pl.BlockSpec((Z, sub_len), lambda s: (0, s))],
        out_specs=pl.BlockSpec((Z, sub_len), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((stripes * Z, sub_len), jnp.uint8),
        interpret=interpret)(out)


class ClayPlugin(ErasureCodePlugin):
    """reference: clay/ErasureCodePluginClay.cc."""

    def factory(self, profile: dict) -> ClayCodec:
        return ClayCodec(profile)
