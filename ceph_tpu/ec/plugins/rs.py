"""Reed-Solomon plugin family — the jerasure/ISA-L analog, TPU-first.

Covers the matrix techniques of the reference's jerasure plugin (reference:
src/erasure-code/jerasure/ErasureCodeJerasure.{h,cc} — one subclass per
technique, each with prepare() building the matrix) and the ISA-L plugin
(reference: src/erasure-code/isa/ErasureCodeIsa.{h,cc}):

    reed_sol_van    ErasureCodeJerasureReedSolomonVandermonde
    reed_sol_r6_op  ErasureCodeJerasureReedSolomonRAID6 (m=2: rows 1, 2^j)
    cauchy_orig     ErasureCodeJerasureCauchyOrig
    cauchy_good     ErasureCodeJerasureCauchyGood

The bitmatrix/packet techniques (liberation, blaum_roth, liber8tion —
reference: jerasure/liberation.c + ErasureCodeJerasureLiberation/
BlaumRoth/Liber8tion) run through BitmatrixCodec: m=2 RAID-6 codes whose
chunks split into w packets XOR-combined per a [2w, kw] GF(2) bitmatrix
(construction + provenance notes: gf/gf2.py), applied on-device through
the same MXU bitplane matmul as the byte codes.

Three interchangeable backends execute the same matrices:
    jax     bitplane GF(2) matmul on TPU (ceph_tpu.ops.bitplane)
    oracle  C++ SIMD split-table path (native/gf_oracle.cc — ISA-L analog)
    numpy   pure-python referee (ceph_tpu.gf.reference_codec)
Parity bytes are identical across backends (byte-wise GF semantics).
"""
from __future__ import annotations

import resource

import numpy as np

from ...common.kernel_telemetry import TELEMETRY
from ...common.tracer import stage
from ...gf.matrix import (
    cauchy_good_coding_matrix,
    cauchy_original_coding_matrix,
    vandermonde_coding_matrix,
)
from ...gf.tables import gf_pow
from ..interface import ErasureCode, InsufficientChunks, InvalidProfile
from ..registry import ErasureCodePlugin

TECHNIQUES = ("reed_sol_van", "reed_sol_r6_op", "cauchy_orig", "cauchy_good")
BITMATRIX_TECHNIQUES = ("liberation", "blaum_roth", "liber8tion")


def build_coding_matrix(technique: str, k: int, m: int) -> np.ndarray:
    if technique == "reed_sol_van":
        return vandermonde_coding_matrix(k, m).astype(np.uint8)
    if technique == "reed_sol_r6_op":
        # reed_sol.c :: reed_sol_r6_coding_matrix — RAID-6: row0 all ones,
        # row1[j] = 2^j
        if m != 2:
            raise InvalidProfile("technique=reed_sol_r6_op requires m=2")
        mat = np.ones((2, k), dtype=np.uint8)
        mat[1] = [gf_pow(2, j) for j in range(k)]
        return mat
    if technique == "cauchy_orig":
        return cauchy_original_coding_matrix(k, m).astype(np.uint8)
    if technique == "cauchy_good":
        return cauchy_good_coding_matrix(k, m).astype(np.uint8)
    raise InvalidProfile(
        f"unknown technique {technique!r}; known: "
        f"{TECHNIQUES + BITMATRIX_TECHNIQUES}"
    )


def _minor_faults() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def _to_host(result) -> np.ndarray:
    """Host copy of a GF apply's device result: the wait for the kernel,
    then the fetch, each its own stage (np.asarray alone does both).
    With telemetry on, the fetch counts into `d2h_fetches` and the
    process's minor page faults during it into `d2h_minor_faults`."""
    with stage("gf.wait"):
        result.block_until_ready()
    with stage("gf.d2h"):
        if not TELEMETRY.enabled:
            return np.asarray(result)
        faults = _minor_faults()
        host = np.asarray(result)
        TELEMETRY.record_d2h(_minor_faults() - faults)
        return host


class RSCodec(ErasureCode):
    """Systematic MDS Reed-Solomon codec over GF(2^8)."""

    def __init__(self, profile: dict | None = None, backend: str = "jax"):
        self.backend = backend
        self._jax_codec = None
        super().__init__(profile)

    def init(self, profile: dict) -> None:
        self.profile = dict(profile)
        self.k = self.parse_int(profile, "k", 2)
        self.m = self.parse_int(profile, "m", 1)
        self.technique = profile.get("technique", "reed_sol_van")
        w = self.parse_int(profile, "w", 8)
        if w != 8:
            raise InvalidProfile(
                f"w={w} unsupported: the TPU bitplane kernel is specialized "
                "for GF(2^8) (w=8), the default in the reference too"
            )
        if self.k < 1 or self.m < 1:
            raise InvalidProfile(f"k={self.k}, m={self.m} must be >= 1")
        self.coding = build_coding_matrix(self.technique, self.k, self.m)
        if self.backend == "jax":
            from ...ops.bitplane import BitplaneCodec

            self._jax_codec = BitplaneCodec(self.coding)

    # -- hot path (reference: ErasureCodeInterface.h :: encode_chunks) ----
    def supports_parity_delta(self) -> bool:
        # byte-wise GF matrix apply: strictly column-local, identity
        # placement — safe for the OSD's RMW parity-delta
        return True

    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        with stage("ec.encode"):
            data_chunks = np.ascontiguousarray(data_chunks, dtype=np.uint8)
            if self.backend == "jax":
                return _to_host(self._jax_codec.encode(data_chunks))
            if self.backend == "oracle":
                from ... import native_oracle

                return native_oracle.encode(self.coding, data_chunks,
                                            fast=True)
            from ...gf.reference_codec import encode_chunks as np_encode

            return np_encode(self.coding, data_chunks)

    def decode_chunks(self, want_to_read, chunks: dict[int, np.ndarray]):
        with stage("ec.decode"):
            return self._decode_chunks(want_to_read, chunks)

    def _decode_chunks(self, want_to_read, chunks: dict[int, np.ndarray]):
        avail = sorted(chunks)
        if len(avail) < self.k:
            raise InsufficientChunks(f"need {self.k}, have {len(avail)}")
        use = avail[: self.k]
        want = sorted(set(want_to_read))
        missing = [w for w in want if w not in chunks]
        survivors = [np.asarray(chunks[r], dtype=np.uint8) for r in use]
        if self.backend == "jax":
            result = self._rebuild_on_device(use, survivors, missing)
        else:
            result = self._rebuild_on_host(use, survivors, missing)
        for wanted in want:
            if wanted in chunks:
                result[wanted] = np.asarray(chunks[wanted], dtype=np.uint8)
        return result

    def _rebuild_on_device(self, use, survivors, missing) -> dict:
        """Only the missing shards, in one apply over the survivors and
        one fetch of [len(missing), L].  The k survivor rows go to the
        device one by one, unstacked: the apply assembles its input
        there."""
        if not missing:
            return {}
        rebuilt = _to_host(
            self._jax_codec.reconstruct(use, survivors, missing))
        return dict(zip(missing, rebuilt))

    def _rebuild_on_host(self, use, survivors, missing) -> dict:
        """The referee backends: all k data rows, then the missing parity
        re-encoded from them."""
        shards = np.stack(survivors)
        if self.backend == "oracle":
            from ... import native_oracle

            data = native_oracle.decode(self.coding, self.k, use, shards)
        else:
            from ...gf.reference_codec import decode_chunks as np_decode

            out = np_decode(self.coding, self.k, dict(zip(use, shards)), want=list(range(self.k)))
            data = np.stack([out[i] for i in range(self.k)])
        result = {w: data[w] for w in missing if w < self.k}
        missing_par = [w for w in missing if w >= self.k]
        if missing_par:
            from ...gf.reference_codec import apply_matrix

            rowmat = np.ascontiguousarray(
                self.coding[[w - self.k for w in missing_par]]
            )
            result.update(zip(missing_par, apply_matrix(rowmat, data)))
        return result


class BitmatrixCodec(ErasureCode):
    """m=2 RAID-6 packet codec for the jerasure bitmatrix techniques
    (reference: ErasureCodeJerasureLiberation et al.: chunks split into w
    packets, parity = GF(2) bitmatrix over packets).  Default w per
    technique follows the reference's ErasureCodeJerasure defaults where
    they exist (liberation/blaum_roth stock w=7; liber8tion w=8)."""

    def __init__(self, profile: dict | None = None, backend: str = "jax"):
        self.backend = backend
        self._dm_cache: dict[tuple, np.ndarray] = {}
        super().__init__(profile)

    def init(self, profile: dict) -> None:
        from ...gf.gf2 import gf2_inv, raid6_bitmatrix

        self._dm_cache.clear()
        self.profile = dict(profile)
        self.k = self.parse_int(profile, "k", 2)
        self.m = self.parse_int(profile, "m", 2)
        self.technique = profile.get("technique", "liberation")
        if self.m != 2:
            raise InvalidProfile(
                f"technique={self.technique} is RAID-6 only (m=2), got "
                f"m={self.m}"
            )
        default_w = 8 if self.technique == "liber8tion" else 7
        self.w = self.parse_int(profile, "w", default_w)
        try:
            self.B = raid6_bitmatrix(self.technique, self.k, self.w)
        except ValueError as e:
            raise InvalidProfile(str(e))
        self._gf2_inv = gf2_inv
        if self.backend == "jax":
            # stable device-cache key, once per codec
            from ...ops.bitplane import matrix_digest

            self._B_digest = matrix_digest(self.B)

    def get_chunk_size(self, stripe_width: int) -> int:
        base = super().get_chunk_size(stripe_width)
        return -(-base // self.w) * self.w  # w packets per chunk

    def _apply(self, M: np.ndarray, rows: np.ndarray,
               mat_key: str | None = None) -> np.ndarray:
        if self.backend == "jax":
            # the packet codes encode inline (not batcher-eligible): the
            # np.asarray is their deliberate codec-seam sync
            import jax.numpy as jnp

            from ...ops.bitplane import apply_matrix_jax

            with stage("gf.h2d"):
                dev = jnp.asarray(rows, dtype=jnp.uint8)
            return np.asarray(apply_matrix_jax(M, dev, mat_key=mat_key))
        out = np.zeros((M.shape[0], rows.shape[1]), dtype=np.uint8)
        for r in range(M.shape[0]):
            for j in np.nonzero(M[r])[0]:
                out[r] ^= rows[j]
        return out

    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        data_chunks = np.ascontiguousarray(data_chunks, dtype=np.uint8)
        k, L = data_chunks.shape
        if L % self.w:
            raise ValueError(f"chunk length {L} not divisible by w={self.w}")
        rows = data_chunks.reshape(k * self.w, L // self.w)
        parity = self._apply(self.B, rows,
                             mat_key=getattr(self, "_B_digest", None))
        return parity.reshape(2, L)

    def decode_chunks(self, want_to_read, chunks: dict[int, np.ndarray]):
        avail = sorted(chunks)
        if len(avail) < self.k:
            raise InsufficientChunks(f"need {self.k}, have {len(avail)}")
        use = avail[: self.k]
        L = len(next(iter(chunks.values())))
        w, k = self.w, self.k
        inv = self._dm_cache.get(tuple(use))
        if inv is None:
            # generator rows: data chunk i = identity block i; parity j =
            # B row block j; per-pattern cache (the ShecTableCache /
            # BitplaneCodec._decode_cache role — at most C(k+2,2) entries)
            G = np.concatenate(
                [np.eye(k * w, dtype=np.uint8), self.B], axis=0
            )
            sel = np.concatenate(
                [G[c * w : (c + 1) * w] for c in use], axis=0
            )  # [kw, kw]
            inv = self._gf2_inv(sel)
            self._dm_cache[tuple(use)] = inv
        rows = np.concatenate([
            np.asarray(chunks[c], dtype=np.uint8).reshape(w, L // w)
            for c in use
        ])
        data_rows = self._apply(inv, rows)
        data = data_rows.reshape(k, L)
        result: dict[int, np.ndarray] = {}
        missing_par = [
            c for c in sorted(set(want_to_read))
            if c >= k and c not in chunks
        ]
        if missing_par:
            par = self._apply(
                np.concatenate(
                    [self.B[(c - k) * w : (c - k + 1) * w]
                     for c in missing_par]
                ),
                data_rows,
            )
            for i, c in enumerate(missing_par):
                result[c] = par[i * w : (i + 1) * w].reshape(L)
        for wanted in sorted(set(want_to_read)):
            if wanted in chunks:
                result[wanted] = np.asarray(chunks[wanted], dtype=np.uint8)
            elif wanted < k:
                result[wanted] = data[wanted]
        return result


class RSPlugin(ErasureCodePlugin):
    """Registry factory (reference: jerasure/ErasureCodePluginJerasure.cc ::
    ErasureCodePluginJerasure::factory switching on technique)."""

    def __init__(self, backend: str = "jax"):
        self.backend = backend

    def factory(self, profile: dict):
        if profile.get("technique") in BITMATRIX_TECHNIQUES:
            return BitmatrixCodec(profile, backend=self.backend)
        return RSCodec(profile, backend=self.backend)
