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

Bit-exactness: all ops are exact integer ops; tests assert parity bytes are
identical to the C++ oracle (native/gf_oracle.cc).
"""
from __future__ import annotations

import hashlib
import os
import time
from collections import OrderedDict
from functools import lru_cache, partial
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


def _bitmatrix_body(B: jnp.ndarray, chunks: jnp.ndarray) -> jnp.ndarray:
    """(rows*8 x n*8) GF(2) matrix times [n, L] byte chunks -> [rows, L]
    — THE encode math, written once and wrapped below (plain, donated,
    and fused variants must never diverge byte-wise)."""
    bits = unpack_bitplanes(chunks)
    acc = jax.lax.dot_general(
        B,
        bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return pack_bitplanes((acc & 1).astype(jnp.uint8))


_apply_bitmatrix = jax.jit(_bitmatrix_body)

#: _apply_bitmatrix with the packed stripe buffer DONATED (SNIPPETS.md
#: [1]/[3] `donation_vector` machinery behind `donate_argnums`): a
#: flush's input buffer is recycled for the kernel's bitplane workspace/
#: output instead of allocating fresh — real on donating backends
#: (TPU/GPU), a no-op annotation on CPU.  The caller must own `chunks`
#: exclusively (the write batcher's pooled pack does; never donate a
#: caller-visible array).
_apply_bitmatrix_donated = jax.jit(_bitmatrix_body, donate_argnums=(1,))


def matrix_digest(mat: np.ndarray) -> str:
    """Stable identity of a coding matrix (shape + bytes) — computed
    ONCE per codec/cached-decode-matrix and used as the device-cache key
    so the hot path stops paying a fresh `mat.tobytes()` host copy per
    stripe (the cephdma satellite fix)."""
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    h = hashlib.sha1(repr(mat.shape).encode())
    h.update(mat.tobytes())
    return h.hexdigest()


#: digest-keyed device bitmatrix cache (LRU, same bound as the legacy
#: tobytes-keyed lru_cache); one lock — lookups are dict reads
_BITMATRIX_BY_KEY: OrderedDict[tuple, jnp.ndarray] = OrderedDict()
_BITMATRIX_LOCK = Lock()
_BITMATRIX_MAX = 256


def _bitmatrix_for(mat: np.ndarray, mat_key: str | None,
                   xor: bool = False) -> jnp.ndarray:
    """Device bitmatrix for `mat`: by precomputed stable digest when the
    caller holds one (codec hot path — no per-call host copy), else the
    legacy tobytes-keyed cache."""
    if mat_key is None:
        m = np.ascontiguousarray(mat, dtype=np.uint8)
        if xor:
            return xor_bitmatrix_device(m.tobytes(), m.shape)
        return bitmatrix_device(m.tobytes(), m.shape)
    key = (mat_key, bool(xor))
    with _BITMATRIX_LOCK:
        B = _BITMATRIX_BY_KEY.get(key)
        if B is not None:
            _BITMATRIX_BY_KEY.move_to_end(key)
            return B
    m = np.ascontiguousarray(mat, dtype=np.uint8)
    B = (jnp.asarray(np.kron(m, np.eye(8, dtype=np.int8))) if xor
         else jnp.asarray(matrix_to_bitmatrix(m), dtype=jnp.int8))
    with _BITMATRIX_LOCK:
        _BITMATRIX_BY_KEY[key] = B
        _BITMATRIX_BY_KEY.move_to_end(key)
        while len(_BITMATRIX_BY_KEY) > _BITMATRIX_MAX:
            _BITMATRIX_BY_KEY.popitem(last=False)
    return B


def apply_matrix_xla(mat: np.ndarray, chunks,
                     mat_key: str | None = None) -> jnp.ndarray:
    """GF(2^8) matrix (rows x n, uint8 elements) applied to byte chunks via
    the XLA bitplane matmul (bitplanes round-trip through HBM).

    Byte-wise GF semantics identical to the oracle's gfo_apply (ISA-L
    convention) for every technique.  `mat_key`: the codec's precomputed
    stable digest of `mat` — skips the per-call tobytes host copy.
    """
    B = _bitmatrix_for(mat, mat_key)
    chunks = jnp.asarray(chunks, dtype=jnp.uint8)
    return _apply_bitmatrix(B, chunks)


# Config-surface override (the `ec_kernel` option): process-wide like the
# env knob it mirrors — kernel dispatch is per-process, not per-daemon,
# so the last daemon to boot with an explicit setting wins.
_kernel_override: str | None = None


def set_kernel_override(mode: str | None) -> None:
    """Force the GF kernel path from config ('xla'/'pallas'; None/'auto'
    clears).  Takes precedence over CEPH_TPU_EC_KERNEL."""
    global _kernel_override
    _kernel_override = None if mode in (None, "auto") else mode


def _want_pallas() -> bool:
    """Kernel dispatch policy (round-4 verdict item #3: the production
    registry -> codec path must reach the fused Pallas kernel on TPU).

    CEPH_TPU_EC_KERNEL: "pallas" / "xla" force a path; default "auto"
    picks the fused kernel on the TPU backend and the XLA gather-free
    bitplane path elsewhere.
    The `ec_kernel` config option sets the same switch programmatically
    (set_kernel_override) and wins over the env var.
    """
    mode = _kernel_override or os.environ.get("CEPH_TPU_EC_KERNEL", "auto")
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
    """The GF kernel auto dispatch would pick right now ('pallas'/'xla')
    — telemetry provenance for call sites above this seam."""
    return "pallas" if _want_pallas() else "xla"


def _apply_matrix_dispatch(mat: np.ndarray, chunks,
                           mat_key: str | None = None,
                           donate: bool = False) -> tuple:
    """(result, backend) — the dispatch body of apply_matrix_jax, split
    out so the telemetry wrapper can attribute the call to the backend
    that served it.  A Pallas failure raises: the kernel's layouts are
    compile-checked for the chip (tests/test_chip_compile.py), so a
    failure here is a fault to see, not one to hide behind XLA.
    `donate=True` routes the XLA path through the donation-enabled jit
    (caller owns `chunks` exclusively — the pooled pack contract); the
    Pallas route ignores it (its VMEM kernel manages its own buffers).
    The whole body is the `gf.dispatch` stage."""
    with stage("gf.dispatch"):
        if _want_pallas():
            from .pallas_gf import apply_matrix_pallas

            from ..common.device_policy import get_device_policy

            # interpret mode only off the chip (CEPH_TPU_EC_KERNEL=pallas
            # on a CPU host); "tpu" is the one backend name that compiles
            return apply_matrix_pallas(
                mat, chunks,
                interpret=get_device_policy().backend() != "tpu",
            ), "pallas"
        if donate:
            # only take the donated jit where the backend honors donation
            # (CPU accepts-and-ignores it, with a warning per shape): the
            # non-donating path is byte-identical, so nothing is lost
            from .device_pool import donation_supported

            if donation_supported():
                B = _bitmatrix_for(mat, mat_key)
                return _apply_bitmatrix_donated(
                    B, jnp.asarray(chunks, dtype=jnp.uint8)), "xla"
        return apply_matrix_xla(mat, chunks, mat_key=mat_key), "xla"


def apply_matrix_dev(mat: np.ndarray, chunks, mat_key: str | None = None,
                     donate: bool = False) -> jnp.ndarray:
    """Device-resident GF(2^8) matrix apply: same kernel dispatch as
    apply_matrix_jax, but the result STAYS a device array and the call
    never blocks — the cephdma async encode seam.  The caller owns the
    single deliberate sync (its commit-point `np.asarray`) and accounts
    it there; this records an async (synced=False) telemetry sample with
    zero host-copy bytes.  `donate=True` recycles `chunks`' device
    buffer into the kernel (the packed-stripe-buffer donation — `chunks`
    must be an exclusively-owned device array; a donated buffer is dead
    to the caller afterward)."""
    tm = TELEMETRY
    if not tm.enabled:
        return _apply_matrix_dispatch(mat, chunks, mat_key, donate)[0]
    t0 = time.perf_counter()
    out, backend = _apply_matrix_dispatch(mat, chunks, mat_key, donate)
    dt = time.perf_counter() - t0
    shape = getattr(chunks, "shape", None)
    tm.record(
        "gf_apply", backend, dt,
        bytes_in=int(getattr(chunks, "nbytes", 0)),
        bytes_out=mat.shape[0] * shape[-1] if shape else 0,
        compiled=tm.first_call(("gf_apply", mat.shape, shape, backend,
                                donate)),
    )
    return out


@lru_cache(maxsize=256)
def _fused_encode_jit(nargs: int, donate: bool):
    """One jitted program per stripe count: commit of the host stripe
    args, the column concat, AND the bitplane encode fuse into a single
    dispatch — the pack never exists as a host staging copy and XLA
    sees the whole flush (donate=True additionally donates every stripe
    arg's committed buffer into the kernel)."""

    def body(B, *chunks):
        x = chunks[0] if len(chunks) == 1 else \
            jnp.concatenate(chunks, axis=1)
        return _bitmatrix_body(B, x)

    return jax.jit(body, donate_argnums=tuple(range(1, nargs + 1))
                   if donate else ())


def fused_bucket(n: int) -> int:
    """The arity fused_encode_async actually dispatches for `n` stripes
    (next power of two; pads are zero stripes) — exposed so the flush
    seam's host-copy accounting can charge the REAL transfer volume,
    pads included."""
    return 1 << max(0, n - 1).bit_length() if n > 1 else 1


def fused_encode_async(mat: np.ndarray, chunks_list,
                       mat_key: str | None = None,
                       donate: bool = False) -> tuple[jnp.ndarray, str]:
    """Fused multi-stripe encode, fully async: [k, L] stripes (host or
    device) -> (ONE device-resident [m, sum(L)] parity array, the
    backend that served it), in ONE dispatch, bit-identical to
    apply_matrix_jax on the host-concatenated pack.  The cephdma flush
    seam: no host staging pack, no fetch — the caller owns the single
    commit-point materialization and its accounting, and records the
    returned backend.  `donate=True` donates the stripes' committed
    buffers into the kernel on backends that honor donation."""
    n = len(chunks_list)
    if _want_pallas():
        # the Pallas VMEM kernel keeps its own packing; hand it the
        # host pack and stay async through apply_matrix_dev
        packed = chunks_list[0] if n == 1 else \
            np.concatenate([np.asarray(c) for c in chunks_list], axis=1)
        return apply_matrix_dev(mat, packed, mat_key=mat_key,
                                donate=donate), "pallas"
    if donate:
        from .device_pool import donation_supported

        donate = donation_supported()
    # bucket the arity to the next power of two with zero stripes (the
    # extra parity columns are zeros past every caller's demux window):
    # a traffic run's stripe counts drift over 1..max_stripes, and an
    # unbucketed jit compiles per DISTINCT count — measured as 200 ms+
    # p99 stalls whenever a novel count appeared mid-run.  7 variants
    # warm quickly; the pad waste is bounded at <2x and the pads are
    # fresh zeros (donation-safe: every donated arg a distinct buffer).
    bucket = fused_bucket(n)
    if bucket > n:
        shape = chunks_list[0].shape
        chunks_list = list(chunks_list) + [
            np.zeros(shape, dtype=np.uint8) for _ in range(bucket - n)]
    B = _bitmatrix_for(mat, mat_key)
    fn = _fused_encode_jit(bucket, donate)
    tm = TELEMETRY
    if not tm.enabled:
        return fn(B, *chunks_list), "xla"
    t0 = time.perf_counter()
    out = fn(B, *chunks_list)
    dt = time.perf_counter() - t0
    # bytes_in counts what was actually committed, pads included
    tm.record(
        "gf_apply", "xla", dt,
        bytes_in=sum(int(getattr(c, "nbytes", 0)) for c in chunks_list),
        bytes_out=mat.shape[0] * n * chunks_list[0].shape[1],
        compiled=tm.first_call(
            ("gf_fused", mat.shape, bucket, chunks_list[0].shape,
             donate)),
    )
    return out, "xla"


def apply_matrix_jax(mat: np.ndarray, chunks,
                     mat_key: str | None = None) -> jnp.ndarray:
    """GF(2^8) matrix apply with kernel dispatch: the fused Pallas VMEM
    kernel on TPU (ops/pallas_gf.py), the XLA bitplane path elsewhere.

    This is the single entry every production codec (rs/shec/clay plugin
    encode/decode/repair) goes through, so `plugin=jax` via the registry
    runs the same kernel the headline bench measures.  A Pallas failure
    raises; nothing falls back to XLA behind the caller's back.
    Call sites that record their own telemetry use apply_matrix_served,
    which also returns the backend that ran.

    Telemetry (docs/observability.md): one `gf_apply` record per call —
    backend, wall time (dispatch-side; JAX queues the launch, so only
    sync call sites above this seam report achieved GiB/s), bytes
    in/out, compile-vs-execute split by first-seen shape.  Disabled:
    one attribute check.  `mat_key`: precomputed stable digest of `mat`
    (matrix_digest) held on the codec — skips the per-call tobytes host
    copy when resolving the cached device bitmatrix.
    """
    return apply_matrix_served(mat, chunks, mat_key)[0]


def apply_matrix_served(mat: np.ndarray, chunks,
                        mat_key: str | None = None) -> tuple:
    """(apply_matrix_jax's result, the backend that served it)."""
    tm = TELEMETRY
    if not tm.enabled:
        return _apply_matrix_dispatch(mat, chunks, mat_key)
    t0 = time.perf_counter()
    out, backend = _apply_matrix_dispatch(mat, chunks, mat_key)
    dt = time.perf_counter() - t0
    shape = getattr(chunks, "shape", None)
    tm.record(
        "gf_apply", backend, dt,
        bytes_in=int(getattr(chunks, "nbytes", 0)),
        bytes_out=mat.shape[0] * shape[-1] if shape else 0,
        compiled=tm.first_call(("gf_apply", mat.shape, shape, backend)),
    )
    return out, backend


@lru_cache(maxsize=256)
def xor_bitmatrix_device(b_bytes: bytes, shape: tuple[int, int]) -> jnp.ndarray:
    """0/1 XOR-combination matrix expanded to bitplane form: each byte
    row mixes independently per bit, so the bit-level operator is
    kron(B, I_8) and the GF(2^8) bitplane kernel serves XOR codes
    (liberation/blaum_roth/liber8tion packets) unchanged."""
    B = np.frombuffer(b_bytes, dtype=np.uint8).reshape(shape)
    return jnp.asarray(np.kron(B, np.eye(8, dtype=np.int8)))


def apply_xor_matrix_jax(B: np.ndarray, rows,
                         mat_key: str | None = None) -> jnp.ndarray:
    """[R, N] 0/1 matrix XOR-combining [N, L] byte rows -> [R, L], on
    device through the same MXU bitplane matmul as the GF(2^8) path.

    On TPU this dispatches through apply_matrix_jax: a 0/1 matrix IS a
    GF(2^8) matrix (multiply-by-1 expands to the identity bitmatrix), so
    the fused Pallas kernel serves the XOR codes unchanged."""
    if _want_pallas():
        return apply_matrix_jax(np.ascontiguousarray(B, dtype=np.uint8),
                                rows, mat_key=mat_key)
    Bd = _bitmatrix_for(B, mat_key, xor=True)
    tm = TELEMETRY
    if not tm.enabled:
        return _apply_bitmatrix(Bd, jnp.asarray(rows, dtype=jnp.uint8))
    t0 = time.perf_counter()
    out = _apply_bitmatrix(Bd, jnp.asarray(rows, dtype=jnp.uint8))
    shape = getattr(rows, "shape", None)
    tm.record(
        "gf_xor", "xla", time.perf_counter() - t0,
        bytes_in=int(getattr(rows, "nbytes", 0)),
        bytes_out=B.shape[0] * shape[-1] if shape else 0,
        compiled=tm.first_call(("gf_xor", B.shape, shape)),
    )
    return out


def apply_xor_matrix_dev(B: np.ndarray, rows, mat_key: str | None = None,
                         donate: bool = False) -> jnp.ndarray:
    """Device-resident variant of apply_xor_matrix_jax (the bitmatrix/
    packet-codec route of the cephdma async seam): result stays on
    device, no sync; `donate=True` recycles `rows`' exclusively-owned
    device buffer through the donation-enabled jit."""
    if _want_pallas():
        return apply_matrix_dev(np.ascontiguousarray(B, dtype=np.uint8),
                                rows, mat_key=mat_key, donate=donate)
    if donate:
        from .device_pool import donation_supported

        donate = donation_supported()
    Bd = _bitmatrix_for(B, mat_key, xor=True)
    fn = _apply_bitmatrix_donated if donate else _apply_bitmatrix
    tm = TELEMETRY
    if not tm.enabled:
        return fn(Bd, jnp.asarray(rows, dtype=jnp.uint8))
    t0 = time.perf_counter()
    out = fn(Bd, jnp.asarray(rows, dtype=jnp.uint8))
    shape = getattr(rows, "shape", None)
    tm.record(
        "gf_xor", "xla", time.perf_counter() - t0,
        bytes_in=int(getattr(rows, "nbytes", 0)),
        bytes_out=B.shape[0] * shape[-1] if shape else 0,
        compiled=tm.first_call(("gf_xor", B.shape, shape, donate)),
    )
    return out


@lru_cache(maxsize=256)
def bitmatrix_device(mat_bytes: bytes, shape: tuple[int, int]) -> jnp.ndarray:
    """Host-expanded bitmatrix, cached per coding matrix (the analog of
    ErasureCodeIsaTableCache's per-pattern table cache, reference:
    src/erasure-code/isa/ErasureCodeIsaTableCache.cc)."""
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(shape)
    return jnp.asarray(matrix_to_bitmatrix(mat), dtype=jnp.int8)


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

    def decode(self, available_rows, shards) -> jnp.ndarray:
        """Rebuild the k data shards from >= k surviving shards.

        available_rows: shard ids (sorted) matching shards' leading rows.
        """
        rows = tuple(int(r) for r in available_rows)
        if len(rows) < self.k:
            raise ValueError(f"need >= {self.k} shards, got {len(rows)}")
        dm, dm_key = self._decode_entry(rows)
        with stage("gf.h2d"):
            shards = jnp.asarray(shards, dtype=jnp.uint8)[: self.k]
        return apply_matrix_jax(dm, shards, mat_key=dm_key)

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
        """
        rows = tuple(int(r) for r in available_rows)
        if len(rows) < self.k:
            raise ValueError(f"need >= {self.k} shards, got {len(rows)}")
        rm, rm_key = self._rebuild_entry(rows, [int(w) for w in want_rows])
        with stage("gf.h2d"):
            shards = jnp.asarray(shards, dtype=jnp.uint8)[: self.k]
        return apply_matrix_jax(rm, shards, mat_key=rm_key)
