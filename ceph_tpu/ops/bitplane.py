"""Bitplane GF(2^8) codec — the TPU-native formulation of RS encode/decode.

Replaces the role of gf-complete's SIMD GF byte kernels (reference:
src/erasure-code/jerasure/gf-complete :: gf_w8 SSE/AVX paths, and
src/isa-l :: ec_encode_data): instead of per-byte GF multiplies (TPU has no
byte multiplier and gathers are slow), every GF(2^8) multiply-by-constant is
expanded once, on the host, into its 8x8 GF(2) bitmatrix
(ceph_tpu.gf.matrix.matrix_to_bitmatrix — the trick jerasure's Cauchy path
uses for XOR scheduling, reference: jerasure.c :: jerasure_matrix_to_bitmatrix).
The whole m x k coding matrix becomes one (m*8) x (k*8) 0/1 matrix B, and

    parity_bitplanes = (B @ data_bitplanes) mod 2

is a single int8 matmul on the MXU with contraction depth k*8 — exactly the
"large, batched" shape XLA tiles well.  Data layout is whole shards
[k, shard_len] (chunk j of every stripe is contiguous on shard j, mirroring
ECBackend's shard layout, reference: src/osd/ECUtil.h :: stripe_info_t), so
one matmul covers every stripe of an object, and shard_len is the batch axis
sharded across chips by ceph_tpu.parallel.

The seam every codec shares is here, in two steps: a host stripe
reaches the device by one `jnp.asarray(x, dtype=jnp.uint8)` under the
`gf.h2d` stage, and `apply_matrix_jax` applies the GF(2^8) matrix to it
(the fused Pallas kernel on TPU, `_apply_bitmatrix` elsewhere), leaving
the result on the device for the caller to fetch.  `commit` is that
first step for RS encode and decode and the SHEC plugin: given a
sequence of 1-D rows (RS decode's k survivors, SHEC's read set) it
commits them in one `jax.device_put`, with no host stack.  The
batchers, the EC backend's ranged decode, the stream pipeline and the
Clay plugin commit under the same stage themselves.

Bit-exactness: all ops are exact integer ops; tests assert parity bytes are
identical to the C++ oracle (native/gf_oracle.cc).
"""
from __future__ import annotations

import hashlib
import os
import time
from collections import OrderedDict
from threading import Lock

import jax
import jax.numpy as jnp
import numpy as np

from ..common.host_heap import retain_freed_large_blocks
from ..common.kernel_telemetry import SENTINEL, TELEMETRY
from ..common.tracer import stage
from ..gf.matrix import decode_matrix_for, matrix_to_bitmatrix, systematic_generator
from ..gf.tables import gf_matmul

_BIT_IDX = np.arange(8, dtype=np.uint8)


def unpack_bitplanes(chunks: jnp.ndarray) -> jnp.ndarray:
    """[n, L] uint8 bytes -> [n*8, L] int8 bitplanes (plane n*8+l = bit l)."""
    n, L = chunks.shape
    bits = (chunks[:, None, :] >> jnp.asarray(_BIT_IDX)[None, :, None]) & 1
    return bits.reshape(n * 8, L).astype(jnp.int8)


def pack_bitplanes(bits: jnp.ndarray) -> jnp.ndarray:
    """[n*8, L] 0/1 -> [n, L] uint8."""
    n8, L = bits.shape
    b = bits.reshape(n8 // 8, 8, L).astype(jnp.uint8)
    return (b << jnp.asarray(_BIT_IDX)[None, :, None]).sum(axis=1, dtype=jnp.uint8)


@jax.jit
def _apply_bitmatrix(B: jnp.ndarray, chunks: jnp.ndarray) -> jnp.ndarray:
    """(rows*8 x n*8) GF(2) matrix times [n, L] byte chunks -> [rows, L]:
    the XLA bitplane apply (bitplanes round-trip through HBM)."""
    bits = unpack_bitplanes(chunks)
    acc = jax.lax.dot_general(
        B,
        bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return pack_bitplanes((acc & 1).astype(jnp.uint8))


def matrix_digest(mat: np.ndarray) -> str:
    """Stable identity of a coding matrix (shape + bytes), computed once
    per codec or cached decode matrix and used as the device-cache key,
    so the hot path pays no `mat.tobytes()` host copy per call."""
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    h = hashlib.sha1(repr(mat.shape).encode())
    h.update(mat.tobytes())
    return h.hexdigest()


#: digest-keyed device bitmatrix cache (LRU, the analog of
#: ErasureCodeIsaTableCache's per-pattern table cache, reference:
#: src/erasure-code/isa/ErasureCodeIsaTableCache.cc); lookups are dict
#: reads under one lock
_BITMATRIX_BY_KEY: OrderedDict[str, jnp.ndarray] = OrderedDict()
_BITMATRIX_LOCK = Lock()
_BITMATRIX_MAX = 256


def _bitmatrix_for(mat: np.ndarray, mat_key: str | None = None) -> jnp.ndarray:
    """Device bitmatrix of `mat`, cached by `mat_key` (the caller's
    precomputed matrix_digest; computed here when it has none).  A 0/1
    matrix needs no path of its own: its bitmatrix is kron(B, I_8), so
    the XOR codes' packet matrices share this cache and the apply."""
    key = mat_key if mat_key is not None else matrix_digest(mat)
    with _BITMATRIX_LOCK:
        B = _BITMATRIX_BY_KEY.get(key)
        if B is not None:
            _BITMATRIX_BY_KEY.move_to_end(key)
            return B
    B = jnp.asarray(matrix_to_bitmatrix(np.ascontiguousarray(mat, np.uint8)),
                    dtype=jnp.int8)
    with _BITMATRIX_LOCK:
        _BITMATRIX_BY_KEY[key] = B
        _BITMATRIX_BY_KEY.move_to_end(key)
        while len(_BITMATRIX_BY_KEY) > _BITMATRIX_MAX:
            _BITMATRIX_BY_KEY.popitem(last=False)
    return B


def _want_pallas() -> bool:
    """Kernel dispatch policy: the production registry -> codec path
    reaches the fused Pallas kernel on TPU.

    CEPH_TPU_EC_KERNEL: "pallas" / "xla" force a path (the benchmark's
    rehearsal and chip_smoke.py force the chip's kernel on the CPU,
    where Pallas runs in interpret mode); the default "auto" picks the
    fused kernel on the TPU backend and the XLA bitplane path elsewhere.
    """
    mode = os.environ.get("CEPH_TPU_EC_KERNEL", "auto")
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    if mode != "auto":
        raise ValueError(
            f"CEPH_TPU_EC_KERNEL={mode!r}: want auto|pallas|xla"
        )
    # the sentinel's latched `degraded` state downgrades auto dispatch:
    # a wedged backend must not be fed fresh Pallas launches (forced
    # modes above still win — the operator said so).  The backend name
    # comes from the policy seam (cephtopo): a cpu-fallback topology
    # keeps auto on the XLA path even on an accelerator box
    from ..common.device_policy import get_device_policy

    return (not SENTINEL.is_degraded
            and get_device_policy().backend() == "tpu")


def current_backend() -> str:
    """The GF kernel dispatch picks right now ('pallas'/'xla') —
    telemetry provenance for call sites above this seam."""
    return "pallas" if _want_pallas() else "xla"


def apply_matrix_jax(mat: np.ndarray, chunks,
                     mat_key: str | None = None) -> jnp.ndarray:
    """The GF(2^8) matrix apply: [rows, n] x [n, L] byte chunks (host or
    device) -> [rows, L] device array.  Never blocks: the caller owns
    the fetch.  `chunks` may instead be a sequence of n 1-D uint8 rows
    of length L, which the apply assembles on the device.

    Every codec (rs/shec/clay/lrc plugins, the XOR packet codes, the
    batchers, the stream pipeline) goes through here, so the registry
    path runs the kernel the benchmark measures: the fused Pallas VMEM
    kernel on TPU (ops/pallas_gf.py), the XLA bitplane apply elsewhere.
    Byte-wise GF semantics identical to the oracle's gfo_apply (ISA-L
    convention) for every technique.  A Pallas failure raises: the
    kernel's layouts are compile-checked for the chip
    (tests/test_chip_compile.py), so nothing falls back to XLA behind
    the caller's back.  `mat_key`: the caller's precomputed
    matrix_digest of `mat` (skips hashing it per call).

    Telemetry (docs/observability.md): one `gf_apply` record per call —
    backend, dispatch-side wall time (JAX queues the launch, so only
    sync call sites above this seam report achieved GiB/s), bytes
    in/out, compile-vs-execute split by first-seen shape.  Disabled:
    one attribute check.  The whole dispatch is the `gf.dispatch`
    stage.
    """
    tm = TELEMETRY
    t0 = time.perf_counter() if tm.enabled else 0.0
    with stage("gf.dispatch"):
        if _want_pallas():
            from ..common.device_policy import get_device_policy
            from .pallas_gf import apply_matrix_pallas

            # interpret mode only off the chip (CEPH_TPU_EC_KERNEL=pallas
            # on a CPU host); "tpu" is the one backend name that compiles
            backend = "pallas"
            out = apply_matrix_pallas(
                mat, chunks,
                interpret=get_device_policy().backend() != "tpu")
        else:
            backend = "xla"
            x = (jnp.stack(chunks) if isinstance(chunks, (list, tuple))
                 else chunks)
            out = _apply_bitmatrix(_bitmatrix_for(mat, mat_key),
                                   jnp.asarray(x, dtype=jnp.uint8))
    if tm.enabled:
        if isinstance(chunks, (list, tuple)):
            shape = (len(chunks), chunks[0].shape[0])
            nbytes = sum(int(r.nbytes) for r in chunks)
        else:
            shape = getattr(chunks, "shape", None)
            nbytes = int(getattr(chunks, "nbytes", 0))
        tm.record(
            "gf_apply", backend, time.perf_counter() - t0,
            bytes_in=nbytes,
            bytes_out=mat.shape[0] * shape[-1] if shape else 0,
            compiled=tm.first_call(("gf_apply", mat.shape, shape, backend)),
        )
    return out


def commit(chunks):
    """A GF apply's input on the device, under one `gf.h2d` stage: one
    [n, L] array committed whole, or a sequence of 1-D uint8 rows of
    length L committed row by row (no host copy stacks them; the apply
    reads them on the device).  Rows stay 1-D: a [1, L] uint8 array may
    be tiled to 32 sublanes on the chip.  RS decode and the SHEC plugin
    commit so."""
    with stage("gf.h2d"):
        if isinstance(chunks, (list, tuple)):
            # one batched, asynchronous commit of the uint8 rows: on a
            # TPU v5e the decode cell's gf.h2d read 1.94 ms a call so,
            # 2.41 with one jnp.asarray a row
            return jax.device_put(list(chunks))  # noqa: CL8
        return jnp.asarray(chunks, dtype=jnp.uint8)


class BitplaneCodec:
    """Encode/decode a systematic RS code on TPU via the bitplane matmul.

    Mirrors the encode_chunks/decode_chunks split of the reference's
    ErasureCodeInterface (reference:
    src/erasure-code/ErasureCodeInterface.h :: encode_chunks, decode_chunks).
    """

    def __init__(self, coding: np.ndarray):
        # the results' host fetches (RS encode and rebuild) land in
        # recycled heap pages, not newly mapped ones
        retain_freed_large_blocks()
        self.coding = np.ascontiguousarray(coding, dtype=np.uint8)
        self.m, self.k = self.coding.shape
        # stable device-cache key, computed ONCE per codec (cephdma: the
        # hot path used to pay a fresh mat.tobytes() host copy per
        # stripe to key the bitmatrix cache)
        self.coding_digest = matrix_digest(self.coding)
        self.generator = systematic_generator(self.coding)
        #: erasure pattern -> (decode matrix, its stable digest)
        self._decode_cache: dict[tuple[int, ...],
                                 tuple[np.ndarray, str]] = {}
        #: (survivors, wanted rows) -> (rebuild matrix, its digest)
        self._rebuild_cache: dict[tuple[tuple[int, ...], tuple[int, ...]],
                                  tuple[np.ndarray, str]] = {}
        self.rebuild_matrix_hits = 0
        self.rebuild_matrix_misses = 0
        self._count_lock = Lock()

    def encode(self, data) -> jnp.ndarray:
        """[k, L] data shards -> [m, L] parity shards (device array)."""
        with stage("gf.h2d"):
            data = jnp.asarray(data, dtype=jnp.uint8)
        if data.shape[0] != self.k:
            raise ValueError(f"expected {self.k} data shards, got {data.shape[0]}")
        return apply_matrix_jax(self.coding, data,
                                mat_key=self.coding_digest)

    def decode_matrix(self, available_rows: tuple[int, ...]) -> np.ndarray:
        """Per-erasure-pattern inverted matrix, host-cached (ISA-L table-cache
        pattern; SURVEY.md §7 'decode-matrix churn')."""
        return self._decode_entry(available_rows)[0]

    def _decode_entry(self, available_rows) -> tuple[np.ndarray, str]:
        key = tuple(available_rows[: self.k])
        ent = self._decode_cache.get(key)
        if ent is None:
            with stage("ec.decode_matrix"):
                dm = decode_matrix_for(self.generator, self.k,
                                       list(key)).astype(np.uint8)
                ent = (dm, matrix_digest(dm))
            self._decode_cache[key] = ent
        return ent

    def _commit(self, shards):
        """The first k survivors on the device (`commit`)."""
        if isinstance(shards, (list, tuple)):
            return commit(shards[: self.k])
        return commit(shards)[: self.k]

    def decode(self, available_rows, shards) -> jnp.ndarray:
        """Rebuild the k data shards from >= k surviving shards.

        available_rows: shard ids (sorted) matching shards' leading rows;
        shards: one [n, L] array or a sequence of 1-D uint8 rows.
        """
        rows = tuple(int(r) for r in available_rows)
        if len(rows) < self.k:
            raise ValueError(f"need >= {self.k} shards, got {len(rows)}")
        dm, dm_key = self._decode_entry(rows)
        return apply_matrix_jax(dm, self._commit(shards), mat_key=dm_key)

    def _rebuild_entry(self, available_rows, want_rows) -> tuple[np.ndarray, str]:
        key = (tuple(available_rows[: self.k]), tuple(want_rows))
        ent = self._rebuild_cache.get(key)
        with self._count_lock:
            if ent is None:
                self.rebuild_matrix_misses += 1
            else:
                self.rebuild_matrix_hits += 1
        TELEMETRY.record_rebuild_matrix(hit=ent is not None)
        if ent is not None:
            return ent
        with stage("ec.decode_matrix"):
            dm = decode_matrix_for(self.generator, self.k, list(key[0]))
            rm = gf_matmul(self.generator[list(key[1])], dm)
            ent = (rm, matrix_digest(rm))
        self._rebuild_cache[key] = ent
        return ent

    def reconstruct(self, available_rows, shards, want_rows) -> jnp.ndarray:
        """Rebuild exactly `want_rows` (data or parity shard ids) from >= k
        surviving shards: [len(want_rows), L] on the device, in one apply.

        Each row of the rebuild matrix is generator[w] times the inverse
        of the survivors' generator rows: row w of the inverse for a data
        shard, coding[w - k] times it for a parity shard (reference:
        src/erasure-code/isa/ErasureCodeIsa.cc ::
        ErasureCodeIsaDefault::isa_decode), cached per pattern.
        `shards` is one [n, L] array or a sequence of 1-D uint8 rows
        (RS decode passes its k survivors so, unstacked).
        """
        rows = tuple(int(r) for r in available_rows)
        if len(rows) < self.k:
            raise ValueError(f"need >= {self.k} shards, got {len(rows)}")
        rm, rm_key = self._rebuild_entry(rows, [int(w) for w in want_rows])
        return apply_matrix_jax(rm, self._commit(shards), mat_key=rm_key)
