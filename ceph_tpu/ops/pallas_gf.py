"""Pallas fused GF(2^8) matrix-apply kernel — the TPU hot-loop (SURVEY.md §7
step 2, HOT LOOP #1 of §3.1).

The XLA bitplane path (ceph_tpu/ops/bitplane.py) materializes the unpacked
bitplanes (8x the data) through HBM; this kernel keeps them in VMEM:

    per L-tile:  load [kG, T] bytes ->
                 8 mask-compares to [8*kG, T] 0/1 int8 (VPU) ->
                 one int8 MXU matmul with the kron-expanded bitmatrix ->
                 mod-2 -> pack bits back to bytes with a tiny bf16 matmul ->
                 store [rows*G, T] bytes

HBM traffic becomes read 1x + write (rows/n)x of the data — the minimum —
instead of ~17x.  Plays the role gf-complete's SIMD kernels play for
jerasure (reference: src/erasure-code/jerasure/gf-complete :: gf_w8 SSE
paths) and ec_encode_data's AVX-512 loops play for ISA-L (reference:
src/isa-l).

Two tricks carry the throughput (22.7 -> ~65 GiB/s for RS(8,4) 1 MiB
shards on the old remote v5e link; not measured on this round's code):

- **Pack-by-matmul**: bit->byte repacking as P @ (acc & 1) with P holding
  2^l weights in bfloat16 (exact: sums <= 255 < 2^8 and bf16 represents
  integers to 2^8), replacing 8 VPU shift+or passes.  int32 matmuls do not
  legalize in Mosaic and int8 cannot hold 128, hence bf16.
- **kron(B, I_G) row grouping**: the natural [k, T] block has only k
  sublanes while int8 tiles are (32, 128), so every VPU op padded 4-8x.
  Each chunk row is split into G column segments stacked vertically
  ([k*G, L/G]) and the bitmatrix becomes its Kronecker expansion with
  I_G.  MXU cycles are unchanged (the array pads K/M to 128 anyway) but
  every elementwise op runs on full tiles.

Layout: segment g of input row j is grouped row g*k + j, so bit l of it
lives at bits row l*kG + (g*k + j); output bit rows are l'*rG + (g*rows +
i).  The stacking is G column slices and one concat (and the output
unstacks the same way): a reshape that splits the minor dimension of a
uint8 device array instead compiles for minutes and tens of GiB of host
memory at 256 MiB on v5e (488 s, 28 GiB).  Given n 1-D rows instead
(RS decode's survivors, committed unstacked), the kernel takes each row
G times, one [T] block of segment g per grid step, and stacks the kG
blocks as the rows of its [kG, T] input in VMEM: the grouped input is
never written to HBM.  The host builds both expanded matrices once per
(mat, G) (lru_cache), the kernel is shape-generic.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..gf.matrix import matrix_to_bitmatrix

DEFAULT_TILE = 8192
# Scoped-VMEM limit the kernel is compiled under (compiler_params below).
# The compiler's default is 16 MiB of v5e's 128 MiB VMEM; the model in
# vmem_estimate is an upper bound (every buffer counted, none reused) that
# reads ~2x the compiler's own figure for RS(8,4) at the 8192 tile, so the
# limit is raised to 32 MiB to keep that tile while every layout the
# picker accepts still fits what the kernel is allowed to allocate.
VMEM_LIMIT = 32 << 20
TILE_FLOOR = 512


def _pick_group(rows: int, n: int) -> int:
    """Segments per row: enough that n*G reaches a full int8 sublane tile
    (32) and the contraction depth n*8*G reaches the 128-wide MXU, capped
    so the expanded bitmatrix stays small."""
    G = 1
    while n * G < 32 or n * 8 * G < 128:
        G *= 2
    return min(G * 2, 64)  # one extra doubling measured fastest on v5e


def _pad32(r: int) -> int:
    return -(-r // 32) * 32


def vmem_estimate(rows: int, n: int, G: int, tile: int) -> int:
    """Upper bound (bytes) on the kernel's scoped VMEM: every buffer of
    the kernel below counted at once, none reused.

    Fixed: the bitmatrix B' (int8) and pack matrix P' (bf16), each
    double-buffered by the pipeline.  Per column: the input and output
    byte blocks (double-buffered, rows padded to the 32-row uint8 tile),
    the unpacked bitplanes (int8), the int32 accumulator, its bf16
    parity view and the packed f32.  The rows input's kG [T] blocks
    take what the block input takes, and their [kG, T] stack in VMEM
    adds kG bytes a column, within the model's ~2x slack (RS decode's
    shape is compiled too).  Checked against the compiler's own
    figure in tests/test_chip_compile.py (which compiles every main-path
    geometry at the layout picked here) and by bisecting the limit on a
    described v5e: RS(8,4)@8192 needs 9.5 MiB (model 18.8),
    CLAY(8,4,d=11) repair@2048 16.4 MiB (model 26.4)."""
    kG, rG = n * G, rows * G
    fixed = 2 * (8 * rG * 8 * kG + rG * 8 * rG * 2)
    per_col = (2 * _pad32(kG) + 2 * _pad32(rG) + 8 * kG
               + (32 + 16 + 4) * rG)
    return fixed + per_col * tile


def _pick_layout(rows: int, n: int, G: int,
                 tile: int = DEFAULT_TILE) -> int:
    """The widest tile <= `tile` whose vmem_estimate fits VMEM_LIMIT (fat
    decode/repair matrices halve it; TILE_FLOOR is the floor)."""
    while tile > TILE_FLOOR and vmem_estimate(rows, n, G, tile) > VMEM_LIMIT:
        tile //= 2
    return tile


@lru_cache(maxsize=256)
def _kron_matrices(
    mat_bytes: bytes, shape: tuple[int, int], G: int
) -> tuple[np.ndarray, np.ndarray]:
    """(B', P'): kron-expanded GF(2) bitmatrix (int8) and bf16 pack matrix."""
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(shape)
    rows, n = shape
    Bbit = matrix_to_bitmatrix(mat)  # [rows*8, n*8], cols j*8+l
    kG, rG = n * G, rows * G
    Bk = np.zeros((rows * 8 * G, n * 8 * G), np.int8)
    g = np.arange(G)
    for i in range(rows):
        for l2 in range(8):
            for j in range(n):
                for l in range(8):
                    if Bbit[i * 8 + l2, j * 8 + l]:
                        Bk[l2 * rG + g * rows + i, l * kG + g * n + j] = 1
    Pk = np.zeros((rG, rows * 8 * G), np.float32)
    for i in range(rows):
        for l2 in range(8):
            Pk[g * rows + i, l2 * rG + g * rows + i] = 1 << l2
    return Bk, Pk


def _unpack_bits(x, kG: int):
    """[kG, T] uint8 -> [8*kG, T] 0/1 int8 bitplanes (VPU mask-compares)."""
    return jnp.stack(
        [(x & jnp.uint8(1 << l) != 0).astype(jnp.int8) for l in range(8)]
    ).reshape(8 * kG, x.shape[1])


def _apply_kernel(B_ref, P_ref, *refs, kG: int):
    """One [kG, T] input block, or kG 1-D [T] segments stacked here in
    VMEM as its rows (the rows input of _apply_grouped)."""
    *x_refs, o_ref = refs
    if len(x_refs) == 1:
        x = x_refs[0][:]
    else:
        x = jnp.stack([r[:] for r in x_refs])
    bits = _unpack_bits(x, kG)
    acc = jax.lax.dot_general(
        B_ref[:],
        bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    par = (acc & 1).astype(jnp.bfloat16)
    packed = jax.lax.dot_general(
        P_ref[:],
        par,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[:] = packed.astype(jnp.int32).astype(jnp.uint8)


@partial(jax.jit, static_argnames=("rows", "n", "G", "tile", "interpret"))
def _apply_grouped(
    B, P, x, rows: int, n: int, G: int, tile: int, interpret: bool
):
    """x: [n, Lp] uint8, Lp a multiple of G*tile -> [rows, Lp] uint8.
    Stacks the G column segments of x as the kernel's [n*G, Lp/G] input
    (row g*n+j = segment g of row j) and unstacks its output.

    x may instead be a tuple of n 1-D uint8 rows of length L -> [rows,
    L]: the rows are padded to a multiple of G*tile here, on the device,
    and the kernel reads segment g of row j straight from the row, as
    row g*n+j of its block, with no [n*G, Lp/G] copy in HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kG, rG = n * G, rows * G
    if isinstance(x, tuple):  # noqa: CL3 — a pytree's structure is static
        L = x[0].shape[0]
        Lp = L + (-L) % (G * tile)
        if Lp != L:
            x = tuple(jnp.pad(r, (0, Lp - L)) for r in x)
        Lg = Lp // G
        steps = Lg // tile
        ins = [r for g in range(G) for r in x]
        x_specs = [pl.BlockSpec((tile,), lambda i, g=g: (g * steps + i,))
                   for g in range(G) for _ in x]
    else:
        L = Lp = x.shape[1]
        if Lp % (G * tile):
            raise ValueError(
                f"length {Lp} not a multiple of G*tile {G * tile}")
        Lg = Lp // G
        ins = [jnp.concatenate([x[:, g * Lg:(g + 1) * Lg]
                                for g in range(G)])]
        x_specs = [pl.BlockSpec((kG, tile), lambda i: (0, i))]
    out = pl.pallas_call(
        partial(_apply_kernel, kG=kG),
        grid=(Lg // tile,),
        in_specs=[
            pl.BlockSpec(B.shape, lambda i: (0, 0)),
            pl.BlockSpec(P.shape, lambda i: (0, 0)),
            *x_specs,
        ],
        out_specs=pl.BlockSpec((rG, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rG, Lg), jnp.uint8),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(B, P, *ins)
    out = jnp.concatenate(
        [out[g * rows:(g + 1) * rows] for g in range(G)], axis=1)
    return out[:, :L] if Lp != L else out


def apply_matrix_pallas(
    mat: np.ndarray,
    chunks,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    """GF(2^8) matrix apply via the fused Pallas kernel.

    Same contract (and bit-exact output) as
    ceph_tpu.ops.bitplane.apply_matrix_jax: [rows, n] x [n, L] -> [rows, L],
    where `chunks` is one [n, L] array or a sequence of n 1-D uint8 rows
    of length L (host or device; assembled and padded on the device).
    """
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    rows, n = mat.shape
    G = _pick_group(rows, n)
    tile = _pick_layout(rows, n, G, tile)
    Bk, Pk = _kron_matrices(mat.tobytes(), mat.shape, G)
    B = jnp.asarray(Bk)
    P = jnp.asarray(Pk, jnp.bfloat16)
    if isinstance(chunks, (list, tuple)):
        L = chunks[0].shape[0]
        if len(chunks) != n or any(r.shape != (L,) or r.dtype != np.uint8
                                   for r in chunks):
            raise ValueError(f"want {n} 1-D uint8 rows of length {L}, got "
                             f"{[(r.shape, r.dtype) for r in chunks]}")
        return _apply_grouped(B, P, tuple(chunks), rows, n, G, tile,
                              interpret)
    L = chunks.shape[1]
    pad = (-L) % (G * tile)
    if isinstance(chunks, np.ndarray):
        chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
        if pad:
            chunks = np.pad(chunks, ((0, 0), (0, pad)))
    else:
        chunks = jnp.asarray(chunks, dtype=jnp.uint8)
        if pad:
            chunks = jnp.pad(chunks, ((0, 0), (0, pad)))
    out = _apply_grouped(B, P, jnp.asarray(chunks), rows, n, G, tile,
                         interpret)
    return out[:, :L] if pad else out
