"""SHEC — Shingled Erasure Code plugin (k, m, c).

Reference: src/erasure-code/shec/ErasureCodeShec.{h,cc} + ShecTableCache —
local parity groups arranged as overlapping "shingles" over the data chunks,
so a single-chunk failure is repaired by reading fewer than k chunks
(SURVEY.md §2.1; Miyamae et al., HotDep 2014).

The coding matrix is upstream's `shec_reedsolomon_coding_matrix`:
jerasure's reed_sol_van matrix (gf/matrix.py) with the data columns
outside each parity's shingle zeroed.  technique=single keeps one group
of m parities with durability estimator c; technique=multiple (the
default) splits them into two groups (m1, c1) and (m - m1, c - c1), the
split whose mean read for one lost chunk (`recovery_efficiency1`) is
least.  Parity row rr of a group (mg, cg) zeroes the columns from
((rr + cg) k / mg) mod k up to, not including, (rr k / mg) mod k,
cyclically.  For (6,3,2) multiple: parity 0 covers d0-d5, parity 1
d0-d2, parity 2 d3-d5, so a lost data chunk or local parity is rebuilt
from 3 chunks and the global parity from 6.

Provenance (SURVEY.md §0): upstream's source was not at hand; this is
written from its construction as recalled, and the benchmark's plain
reference (benchmark/reference/shec_ref.py) is written from the same.

minimum_to_decode searches parity subsets whose windows cover what must
be solved, least chunks read first (ErasureCodeShec::minimum_to_decode's
role).  The whole recovery — windowed solve plus parity re-encode — is
GF-linear in the chunks read, so decode caches one matrix per (wanted,
available) pattern (the ErasureCodeShecTableCache role) and applies it
on the device: the read set's rows are committed unstacked under
`gf.h2d` (ops/bitplane.py `commit`), the cached matrix is applied with
its digest as the device-cache key, and the result is fetched through
`_to_host` (`gf.wait`, `gf.d2h`), as RS decode does.
"""
from __future__ import annotations

import itertools

import numpy as np

from ...common.host_heap import retain_freed_large_blocks
from ...common.kernel_telemetry import TELEMETRY
from ...common.tracer import stage
from ...gf.matrix import gf_rank, gf_solve, vandermonde_coding_matrix
from ...gf.tables import GF_MUL_TABLE
from ...ops.bitplane import apply_matrix_jax, commit, matrix_digest
from ..interface import ErasureCode, InsufficientChunks, InvalidProfile
from ..registry import ErasureCodePlugin
from .rs import _to_host

TECHNIQUES = ("multiple", "single")


def recovery_efficiency1(k: int, m1: int, m2: int, c1: int,
                         c2: int) -> float:
    """ErasureCodeShec::shec_calc_recovery_efficiency1: the mean number
    of chunks read to rebuild one lost chunk over the k + m1 + m2."""
    r_eff = [10 ** 8] * k
    total = 0
    for mg, cg in ((m1, c1), (m2, c2)):
        for rr in range(mg):
            first, last = rr * k // mg, (rr + cg) * k // mg
            cc = first % k
            while True:
                r_eff[cc] = min(r_eff[cc], last - first)
                cc = (cc + 1) % k
                if cc == last % k:
                    break
            total += last - first
    return (total + sum(r_eff)) / (k + m1 + m2)


def parity_groups(k: int, m: int, c: int,
                  technique: str) -> tuple[int, int]:
    """(m1, c1) of the first parity group; (0, 0) for one group of m.
    multiple: the least recovery_efficiency1 over c1 <= c/2, m1 <= m,
    each group with parities and overlap both or neither; the first
    such split on a tie."""
    if technique == "single":
        return 0, 0
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


def shec_coding_matrix(k: int, m: int, c: int,
                       technique: str = "multiple") -> np.ndarray:
    """m x k shec_reedsolomon_coding_matrix: reed_sol_van with each
    parity's columns outside its shingle zeroed."""
    m1, c1 = parity_groups(k, m, c, technique)
    mat = vandermonde_coding_matrix(k, m)
    for first, mg, cg in ((0, m1, c1), (m1, m - m1, c - c1)):
        for rr in range(mg):
            end = rr * k // mg % k
            cc = (rr + cg) * k // mg % k
            while cc != end:
                mat[first + rr, cc] = 0
                cc = (cc + 1) % k
    return mat


class ShecCodec(ErasureCode):
    def __init__(self, profile: dict | None = None):
        # the decode's host fetch lands in recycled heap pages, not newly
        # mapped ones (the same policy the RS and Clay codecs apply)
        retain_freed_large_blocks()
        self._plan_cache: dict[tuple, tuple] = {}
        #: (wanted, available) -> (decode matrix, its digest, read set)
        self._dm_cache: dict[tuple, tuple[np.ndarray, str, tuple]] = {}
        super().__init__(profile)

    def init(self, profile: dict) -> None:
        self._plan_cache.clear()  # re-init invalidates cached geometry
        self._dm_cache.clear()
        self.profile = dict(profile)
        self.k = self.parse_int(profile, "k", 4)
        self.m = self.parse_int(profile, "m", 3)
        self.c = self.parse_int(profile, "c", 2)
        self.technique = profile.get("technique", "multiple")
        if self.technique not in TECHNIQUES:
            raise InvalidProfile(
                f"technique={self.technique!r}; known: {TECHNIQUES}")
        if not (1 <= self.c <= self.m <= self.k):
            raise InvalidProfile(
                f"SHEC requires 1 <= c <= m <= k, got k={self.k} m={self.m} c={self.c}"
            )
        if self.k + self.m > 255:
            raise InvalidProfile("k+m must be <= 255")
        self.coding = shec_coding_matrix(self.k, self.m, self.c,
                                         self.technique)
        self._coding_u8 = self.coding.astype(np.uint8)
        self._coding_key = matrix_digest(self._coding_u8)

    # -- encode -----------------------------------------------------------
    def supports_parity_delta(self) -> bool:
        return True  # byte-matrix apply, column-local, identity layout

    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        return _to_host(apply_matrix_jax(
            self._coding_u8, commit(np.ascontiguousarray(data_chunks,
                                                         np.uint8)),
            mat_key=self._coding_key))

    # -- recovery plan search (ErasureCodeShec::minimum_to_decode role) ---
    def _window(self, p: int) -> set[int]:
        return {int(j) for j in np.nonzero(self.coding[p])[0]}

    def _requirements(
        self, want: frozenset[int], available: frozenset[int]
    ) -> tuple[list[int], set[int]]:
        """(data chunks that must be solved for, available window data that
        wanted-parity re-encode additionally reads)."""
        avail_data = {a for a in available if a < self.k}
        want_data_missing = {w for w in want if w < self.k} - available
        want_parity_missing = {
            w - self.k for w in want if w >= self.k and w not in available
        }
        parity_window: set[int] = set()
        for p in want_parity_missing:
            parity_window |= self._window(p)
        solve_targets = sorted(want_data_missing | (parity_window - avail_data))
        return solve_targets, parity_window & avail_data

    def _recovery_plan(
        self, want: frozenset[int], available: frozenset[int]
    ) -> tuple[tuple[int, ...], tuple[int, ...]]:
        """Minimal read set: smallest parity subset whose windows cover the
        solve targets with available data and whose coefficient submatrix has
        full rank, plus the window data wanted parities re-encode from."""
        key = (want, available)
        plan = self._plan_cache.get(key)
        if plan is None:
            with stage("shec.decode_matrix"):
                plan = self._search_plan(want, available)
            self._plan_cache[key] = plan
        return plan

    def _search_plan(self, want, available):
        solve_targets, parity_read = self._requirements(want, available)
        if not solve_targets:
            return tuple(sorted(parity_read)), ()
        avail_parities = sorted(a - self.k for a in available if a >= self.k)
        avail_data = {a for a in available if a < self.k}
        targets = set(solve_targets)
        for n_par in range(len(solve_targets), len(avail_parities) + 1):
            best: tuple | None = None
            for parities in itertools.combinations(avail_parities, n_par):
                cols: set[int] = set()
                for p in parities:
                    cols |= self._window(p)
                if (cols - targets) - avail_data:
                    continue  # a window needs data that is neither available
                    # nor being solved for
                A = np.stack([self.coding[p, solve_targets] for p in parities])
                if gf_rank(A) < len(solve_targets):
                    continue
                read_data = ((cols - targets) & avail_data) | parity_read
                cost = len(read_data) + n_par
                if best is None or cost < best[0]:
                    best = (cost, tuple(sorted(read_data)), tuple(parities))
            if best is not None:
                return best[1], best[2]
        raise InsufficientChunks(
            f"SHEC cannot recover {sorted(want)} from {sorted(available)}"
        )

    def _read_set(self, want: frozenset[int],
                  available: frozenset[int]) -> tuple[int, ...]:
        if want <= available:
            return tuple(sorted(want))
        read_data, parities = self._recovery_plan(want, available)
        chunks = set(read_data) | {self.k + p for p in parities}
        return tuple(sorted(chunks | (want & available)))

    def minimum_to_decode(self, want_to_read, available):
        read = self._read_set(frozenset(want_to_read), frozenset(available))
        return {c: [(0, -1)] for c in read}

    def _decode_matrix(
        self, want: frozenset[int], avail_t: tuple[int, ...]
    ) -> np.ndarray:
        """[n_want, n_read] GF(2^8) matrix M with wanted = M @ the read
        set's chunks (`_decode_entry`)."""
        return self._decode_entry(want, avail_t)[0]

    def _decode_entry(
        self, want: frozenset[int], avail_t: tuple[int, ...]
    ) -> tuple[np.ndarray, str, tuple[int, ...]]:
        """(M, its digest, read set): the read set is the plan's chunks
        among `avail_t`, and M maps them to the wanted chunks, cached per
        pattern (the ShecTableCache role, reference:
        shec/ErasureCodeShecTableCache.cc, upgraded from decode-matrix
        caching to whole-plan caching)."""
        key = (want, avail_t)
        ent = self._dm_cache.get(key)
        TELEMETRY.record_shec_matrix(hit=ent is not None)
        if ent is None:
            read = self._read_set(want, frozenset(avail_t))
            with stage("shec.decode_matrix"):
                M = self._build_matrix(want, read)
                ent = (M, matrix_digest(M), read)
            self._dm_cache[key] = ent
        return ent

    def _build_matrix(self, want: frozenset[int],
                      read: tuple[int, ...]) -> np.ndarray:
        avail = frozenset(read)
        solve_targets, _ = self._requirements(want, avail)
        _, parities = self._recovery_plan(want, avail)
        n_in = len(read)
        pos = {c: i for i, c in enumerate(read)}
        rowX: dict[int, np.ndarray] = {}
        if solve_targets:
            # express each windowed-parity equation's RHS as a coefficient
            # row over the chunks read, then solve for the targets
            A = np.stack([self.coding[p, solve_targets] for p in parities])
            Bcoef = np.zeros((len(parities), n_in), dtype=np.int64)
            for r, p in enumerate(parities):
                Bcoef[r, pos[self.k + p]] ^= 1
                for j in self._window(p):
                    if j in solve_targets:
                        continue
                    Bcoef[r, pos[j]] ^= int(self.coding[p, j])
            X = gf_solve(A, Bcoef)  # [n_targets, n_in]
            for idx, j in enumerate(solve_targets):
                rowX[j] = X[idx].astype(np.int64)

        def data_row(j: int) -> np.ndarray:
            if j in rowX:
                return rowX[j]
            e = np.zeros(n_in, dtype=np.int64)
            e[pos[j]] = 1
            return e

        rows = []
        for w in sorted(want):
            if w in pos:
                e = np.zeros(n_in, dtype=np.int64)
                e[pos[w]] = 1
                rows.append(e)
            elif w < self.k:
                rows.append(data_row(w))
            else:
                p = w - self.k
                r = np.zeros(n_in, dtype=np.int64)
                for j in self._window(p):
                    r ^= GF_MUL_TABLE[int(self.coding[p, j]), data_row(j)]
                rows.append(r)
        return np.stack(rows).astype(np.uint8)

    def decode_chunks(self, want_to_read, chunks):
        """The wanted chunks from the plan's chunks among `chunks`: one
        apply of the cached matrix over the read set's rows, committed
        unstacked, and one fetch."""
        with stage("shec.decode"):
            want = frozenset(want_to_read)
            TELEMETRY.record_shec_chunks_read(len(chunks))
            M, key, read = self._decode_entry(want, tuple(sorted(chunks)))
            rows = [np.asarray(chunks[c], dtype=np.uint8) for c in read]
            out = _to_host(apply_matrix_jax(M, commit(rows), mat_key=key))
            return {w: out[i] for i, w in enumerate(sorted(want))}


class ShecPlugin(ErasureCodePlugin):
    """reference: shec/ErasureCodePluginShec.cc."""

    def factory(self, profile: dict) -> ShecCodec:
        return ShecCodec(profile)
