"""Pallas fused GF(2^8) kernel tests (SURVEY.md §7 step 2).

Round-1 verdict: the kernel had zero coverage and silently fell back to
XLA when Mosaic failed to compile it.  These tests pin:
  - bit-exactness vs the reference codec in interpret mode (runs on CPU),
  - the x64 regression: the kernel must still trace with the CRUSH mapper
    imported (round 1's global jax_enable_x64 flip broke Mosaic),
  - padding / non-tile-multiple lengths.

Interpret mode cannot see what the chip's compiler refuses (scoped VMEM,
tiling); tests/test_chip_compile.py compiles the main-path layouts for a
described v5e, and chip_smoke.py runs them on the chip.
"""
import numpy as np
import pytest

from ceph_tpu.gf.matrix import (
    cauchy_good_coding_matrix,
    decode_matrix_for,
    vandermonde_coding_matrix,
    systematic_generator,
)
from ceph_tpu.gf.reference_codec import apply_matrix as apply_ref
from ceph_tpu.ops.pallas_gf import apply_matrix_pallas


def _rand(k, L, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (k, L), dtype=np.uint8)


@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 4)])
def test_interpret_encode_bit_exact(k, m):
    coding = np.ascontiguousarray(cauchy_good_coding_matrix(k, m), np.uint8)
    data = _rand(k, 8192, seed=k * 10 + m)
    out = np.asarray(
        apply_matrix_pallas(coding, data, tile=2048, interpret=True)
    )
    np.testing.assert_array_equal(out, apply_ref(coding, data))


def test_interpret_reed_sol_van_bit_exact():
    k, m = 6, 3
    coding = np.ascontiguousarray(vandermonde_coding_matrix(k, m), np.uint8)
    data = _rand(k, 4096, seed=7)
    out = np.asarray(
        apply_matrix_pallas(coding, data, tile=1024, interpret=True)
    )
    np.testing.assert_array_equal(out, apply_ref(coding, data))


def test_interpret_decode_roundtrip():
    """Erase m shards, decode with the inverted matrix via the kernel."""
    k, m = 8, 4
    coding = np.ascontiguousarray(cauchy_good_coding_matrix(k, m), np.uint8)
    data = _rand(k, 2048, seed=3)
    parity = apply_ref(coding, data)
    shards = np.vstack([data, parity])
    lost = {1, 4, 9, 11}
    avail = [i for i in range(k + m) if i not in lost][:k]
    dm = decode_matrix_for(systematic_generator(coding), k, avail)
    rec = np.asarray(
        apply_matrix_pallas(
            np.ascontiguousarray(dm, np.uint8), shards[avail],
            tile=1024, interpret=True,
        )
    )
    np.testing.assert_array_equal(rec, data)


def test_non_tile_multiple_length_padded():
    k, m = 4, 2
    coding = np.ascontiguousarray(cauchy_good_coding_matrix(k, m), np.uint8)
    data = _rand(k, 3000, seed=5)  # not a multiple of any pow2 tile
    out = np.asarray(
        apply_matrix_pallas(coding, data, tile=1024, interpret=True)
    )
    np.testing.assert_array_equal(out, apply_ref(coding, data))


@pytest.mark.parametrize("L,read_only", [
    (8192, False), (3000, False), (8192, True),
], ids=["tile_multiple", "padded_on_device", "read_only_rows"])
def test_rows_bit_exact_with_one_array(L, read_only):
    """n 1-D rows, assembled (and padded) on the device inside the
    apply, give the bytes of the same rows passed as one [n, L] array;
    read-only host rows (np.frombuffer) are taken as they are."""
    k, m = 8, 4
    coding = np.ascontiguousarray(cauchy_good_coding_matrix(k, m), np.uint8)
    data = _rand(k, L, seed=L + read_only)
    rows = ([np.frombuffer(data[j].tobytes(), np.uint8) for j in range(k)]
            if read_only else list(data))
    got = np.asarray(apply_matrix_pallas(coding, rows, tile=1024,
                                         interpret=True))
    want = np.asarray(apply_matrix_pallas(coding, data, tile=1024,
                                          interpret=True))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, apply_ref(coding, data))


def test_rows_of_unequal_length_refused():
    """Rows the kernel would read past the end of are refused."""
    coding = np.ascontiguousarray(cauchy_good_coding_matrix(4, 2), np.uint8)
    rows = list(_rand(4, 2048))
    rows[2] = rows[2][:1024]
    with pytest.raises(ValueError, match="1-D uint8 rows"):
        apply_matrix_pallas(coding, rows, tile=1024, interpret=True)


def test_fat_matrix_bit_exact():
    """Fat repair matrices (CLAY(8,4,d=11) is [64, 176]) must stay
    bit-exact vs the reference at a tile the layout picker may shrink
    them to."""
    rng = np.random.default_rng(11)
    mat = rng.integers(0, 256, (64, 176), np.uint8)
    data = rng.integers(0, 256, (176, 512), np.uint8)
    out = np.asarray(apply_matrix_pallas(mat, data, tile=256,
                                         interpret=True))
    np.testing.assert_array_equal(out, apply_ref(mat, data))


def test_ragged_matrix_bit_exact():
    """Row and column counts off every tile multiple: the row grouping and
    length padding must be invisible in the output."""
    rng = np.random.default_rng(12)
    mat = rng.integers(0, 256, (13, 40), np.uint8)
    data = rng.integers(0, 256, (40, 300), np.uint8)
    out = np.asarray(apply_matrix_pallas(mat, data, tile=256,
                                         interpret=True))
    np.testing.assert_array_equal(out, apply_ref(mat, data))


# ---- layout picker vs the scoped-VMEM limit -------------------------------
# vmem_estimate is an upper bound on what the compiler allocates, and the
# kernel compiles under VMEM_LIMIT; tests/test_chip_compile.py compiles
# the picked layouts of the main-path geometries for a described v5e.

def test_vmem_model_rejects_clay_at_wide_tiles():
    """CLAY repair [64, 176] at tile 4096 needs 26.2 MiB by the compiler's
    own count — over the default 16 MiB scoped limit — and the model must
    not let it through."""
    from ceph_tpu.ops.pallas_gf import VMEM_LIMIT, _pick_group, vmem_estimate

    G = _pick_group(64, 176)
    assert vmem_estimate(64, 176, G, 4096) > VMEM_LIMIT
    assert vmem_estimate(64, 176, G, 4096) > 26 << 20


def test_layout_picker_shrinks_fat_matrix_tile():
    from ceph_tpu.ops.pallas_gf import (
        VMEM_LIMIT,
        _pick_group,
        _pick_layout,
        vmem_estimate,
    )

    G = _pick_group(64, 176)
    tile = _pick_layout(64, 176, G)
    assert tile == 2048
    assert vmem_estimate(64, 176, G, tile) <= VMEM_LIMIT


def test_layout_picker_keeps_flagship_shapes():
    """RS(8,4) and RS(2,1) keep the 8192 tile."""
    from ceph_tpu.ops.pallas_gf import (
        VMEM_LIMIT,
        _pick_group,
        _pick_layout,
        vmem_estimate,
    )

    for rows, n in [(4, 8), (1, 2)]:
        G = _pick_group(rows, n)
        tile = _pick_layout(rows, n, G)
        assert tile == 8192, (rows, n, tile)
        assert vmem_estimate(rows, n, G, tile) <= VMEM_LIMIT


def test_every_picked_layout_fits_limit_sweep():
    """Property sweep: whatever (rows, n) a codec throws at the picker,
    the chosen tile's VMEM bound fits the limit (or the tile is at its
    floor — the compiler's own error is then the backstop)."""
    from ceph_tpu.ops.pallas_gf import (
        TILE_FLOOR,
        VMEM_LIMIT,
        _pick_group,
        _pick_layout,
        vmem_estimate,
    )

    for rows in (1, 2, 4, 8, 16, 64, 128):
        for n in (2, 8, 20, 176, 256):
            G = _pick_group(rows, n)
            tile = _pick_layout(rows, n, G)
            est = vmem_estimate(rows, n, G, tile)
            assert est <= VMEM_LIMIT or tile <= TILE_FLOOR, \
                (rows, n, tile, est)


def test_kernel_traces_with_crush_mapper_imported():
    """Round-1 regression: crush.mapper flipped jax_enable_x64 globally at
    import, which leaked i64 into the Pallas BlockSpec index maps and made
    Mosaic fail to legalize `func.return (i64, i64)` on real TPUs.  x64 is
    now scoped; importing the mapper (and running a batched CRUSH trace)
    must leave the kernel traceable."""
    import jax

    from ceph_tpu.crush import (
        CompiledCrushMap,
        build_hierarchical_map,
        crush_do_rule_batch,
    )

    cmap = build_hierarchical_map(2, 2)
    cm = CompiledCrushMap(cmap)
    w = np.full(4, 0x10000, np.int64)
    crush_do_rule_batch(cm, 0, np.arange(64), 2, w)  # runs an x64 trace
    assert not jax.config.jax_enable_x64, "x64 leaked out of the CRUSH scope"

    k, m = 8, 4
    coding = np.ascontiguousarray(cauchy_good_coding_matrix(k, m), np.uint8)
    data = _rand(k, 2048, seed=9)
    out = np.asarray(
        apply_matrix_pallas(coding, data, tile=1024, interpret=True)
    )
    np.testing.assert_array_equal(out, apply_ref(coding, data))
