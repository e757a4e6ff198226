"""The GF seam: one host->device commit and one GF(2^8) apply.

Fast, unit-level (no clusters) — the tier-1 budget rule.  Covers: the
apply's round trip bit-identical to the numpy referee for RS(8,4) and
for 0/1 XOR matrices (whose bitmatrix is kron(B, I_8)), the XOR packet
codes and two RS techniques round-tripping through the registry, the
digest-keyed device bitmatrix cache, the write batcher's async encode
(demux, mixed geometries, digest keying, telemetry split between the
flush and the commit-point fetch), stream_encode parity, the decode's
rebuild-matrix cache hits, and the CL8 op-path host-trip audit's TP/TN
fixtures.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from ceph_tpu.common.context import CephContext
from ceph_tpu.common.kernel_telemetry import TELEMETRY
from ceph_tpu.gf.matrix import cauchy_good_coding_matrix, matrix_to_bitmatrix
from ceph_tpu.gf.reference_codec import apply_matrix as ref_apply
from ceph_tpu.ops import bitplane as bp
from ceph_tpu.ops.pipeline import stream_encode
from ceph_tpu.osd.write_batcher import WriteBatcher

RNG = np.random.default_rng(20260804)
MAT84 = cauchy_good_coding_matrix(8, 4).astype(np.uint8)
KEY84 = bp.matrix_digest(MAT84)
MAT42 = cauchy_good_coding_matrix(4, 2).astype(np.uint8)
KEY42 = bp.matrix_digest(MAT42)


def _stripes(n, k=8, L=256):
    return [RNG.integers(0, 256, (k, L), dtype=np.uint8)
            for _ in range(n)]


def _batcher(**overrides):
    conf = {"ec_batch_window_ms": 50.0, "ec_batch_max_stripes": 64,
            "ec_batch_max_bytes": 8 << 20}
    conf.update(overrides)
    cct = CephContext("osd.gf", overrides=conf)
    b = WriteBatcher(cct, entity="osd.gf")
    b.start()
    return b


def _xor_ref(B, rows):
    out = np.zeros((B.shape[0], rows.shape[1]), np.uint8)
    for r in range(B.shape[0]):
        for j in np.nonzero(B[r])[0]:
            out[r] ^= rows[j]
    return out


def _codec(profile):
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    return ErasureCodePluginRegistry.instance().factory(profile)


# -- the apply ---------------------------------------------------------------

def test_plain_roundtrip_bit_identical_rs84():
    x = _stripes(1)[0]
    ref = ref_apply(MAT84, x)
    # host input, and a committed device input with and without a key
    import jax.numpy as jnp

    dev = jnp.asarray(x, dtype=jnp.uint8)
    for chunks, key in ((x, KEY84), (dev, KEY84), (dev, None)):
        out = np.asarray(bp.apply_matrix_jax(MAT84, chunks, mat_key=key))
        assert (out == ref).all()


def test_xor_bitmatrix_route_bit_identical():
    B = RNG.integers(0, 2, (14, 56)).astype(np.uint8)
    rows = RNG.integers(0, 256, (56, 128), dtype=np.uint8)
    out = np.asarray(bp.apply_matrix_jax(B, rows,
                                         mat_key=bp.matrix_digest(B)))
    assert (out == _xor_ref(B, rows)).all()


def test_xor_bitmatrix_is_kron():
    # a 0/1 matrix is a GF(2^8) matrix: multiply-by-1 expands to I_8,
    # so the XOR codes need no bitmatrix of their own
    for shape in ((14, 56), (2, 3), (16, 64)):
        B = RNG.integers(0, 2, shape).astype(np.uint8)
        assert (matrix_to_bitmatrix(B)
                == np.kron(B, np.eye(8, dtype=np.uint8))).all()


def test_matrix_digest_stable_and_distinct():
    assert bp.matrix_digest(MAT84) == KEY84
    assert bp.matrix_digest(MAT84.copy()) == KEY84
    assert bp.matrix_digest(MAT42) != KEY84
    # same bytes, different shape -> different identity
    assert bp.matrix_digest(MAT84.reshape(8, 4)) != KEY84


# -- the registry codecs through the one apply --------------------------------

@pytest.mark.parametrize("technique,w", [("liberation", "7"),
                                         ("blaum_roth", "6"),
                                         ("liber8tion", "8")])
def test_xor_code_roundtrip(technique, w, monkeypatch):
    codec = _codec({"plugin": "jax", "k": "4", "m": "2",
                    "technique": technique, "w": w})
    seen = []
    real = bp.apply_matrix_jax

    def spy(mat, chunks, **kw):
        seen.append(np.asarray(mat).max())
        return real(mat, chunks, **kw)

    monkeypatch.setattr(bp, "apply_matrix_jax", spy)
    data = bytes(RNG.integers(0, 256, 4 * codec.w * 64, dtype=np.uint8))
    enc = codec.encode(set(range(6)), data)
    # the packet matrices reach the apply as plain 0/1 matrices
    assert seen and max(seen) == 1
    for lost in ((0, 1), (2, 5), (4, 5)):
        avail = {i: enc[i] for i in range(6) if i not in lost}
        dec = codec.decode(set(range(6)), avail, len(enc[0]))
        for i in range(6):
            assert np.asarray(dec[i], np.uint8).tobytes() == \
                np.asarray(enc[i], np.uint8).tobytes(), (technique, lost, i)


@pytest.mark.parametrize("technique", ["cauchy_good", "reed_sol_r6_op"])
def test_decode_roundtrip(technique):
    codec = _codec({"plugin": "jax", "k": "4", "m": "2",
                    "technique": technique})
    data = bytes(RNG.integers(0, 256, 4 * 4096, dtype=np.uint8))
    enc = codec.encode(set(range(6)), data)
    for lost in ((0,), (1, 4), (3, 5), (0, 2)):
        avail = {i: enc[i] for i in range(6) if i not in lost}
        dec = codec.decode(set(range(6)), avail, len(enc[0]))
        for i in range(6):
            assert np.asarray(dec[i], np.uint8).tobytes() == \
                np.asarray(enc[i], np.uint8).tobytes(), (technique, lost, i)


# -- the async batcher path --------------------------------------------------

def test_async_demux_identical_to_inline():
    stripes = _stripes(6)
    refs = [ref_apply(MAT84, s) for s in stripes]
    b = _batcher(ec_batch_max_stripes=len(stripes))
    try:
        tickets = [b.encode_submit(MAT84, s, mat_key=KEY84)
                   for s in stripes]
        outs = [b.encode_wait(t) for t in tickets]
    finally:
        b.stop()
    for o, r in zip(outs, refs):
        assert isinstance(o, np.ndarray)
        assert (np.asarray(o) == r).all()
    assert b.stats()["flushes"] >= 1
    # a stopped batcher encodes inline: same bytes
    inline = [b.encode_chunks(MAT84, s, mat_key=KEY84) for s in stripes]
    for o, r in zip(inline, refs):
        assert (np.asarray(o) == r).all()
    assert b.stats()["inline"] == len(stripes)


def test_mixed_geometry_flushes():
    big = _stripes(4, k=8, L=256)
    small = _stripes(3, k=4, L=128)
    b = _batcher(ec_batch_max_stripes=16)
    try:
        tickets = [b.encode_submit(MAT84, s, mat_key=KEY84) for s in big] \
            + [b.encode_submit(MAT42, s, mat_key=KEY42) for s in small]
        outs = [b.encode_wait(t) for t in tickets]
    finally:
        b.stop()
    for o, s, m in zip(outs, big + small, [MAT84] * 4 + [MAT42] * 3):
        assert (np.asarray(o) == ref_apply(m, s)).all()


def test_group_keying_by_digest_not_identity():
    # two DIFFERENT matrices with the same shape must not fuse into one
    # group even when both carry digests (correctness of the key)
    s84 = _stripes(2, k=8, L=128)
    mat_b = cauchy_good_coding_matrix(8, 4).astype(np.uint8).copy()
    mat_b[0, 0] ^= 0x55  # distinct matrix, same geometry
    key_b = bp.matrix_digest(mat_b)
    assert key_b != KEY84
    b = _batcher(ec_batch_max_stripes=8)
    try:
        t1 = b.encode_submit(MAT84, s84[0], mat_key=KEY84)
        t2 = b.encode_submit(mat_b, s84[1], mat_key=key_b)
        o1, o2 = b.encode_wait(t1), b.encode_wait(t2)
    finally:
        b.stop()
    assert (np.asarray(o1) == ref_apply(MAT84, s84[0])).all()
    assert (np.asarray(o2) == ref_apply(mat_b, s84[1])).all()


@pytest.mark.parametrize("n", [4, 3])
def test_telemetry_counters_move_and_sync_split(n):
    stripes = _stripes(n)
    TELEMETRY.enable(True)

    def flush_stats():
        d = TELEMETRY.dump()
        return (d.get("ec_batch_flush", {}), d.get("encode_wait", {}))

    f0, w0 = flush_stats()
    # a long window: the flush fires when the last stripe fills the cap
    b = _batcher(ec_batch_window_ms=2000.0, ec_batch_max_stripes=n)
    try:
        outs = [b.encode_wait(t) for t in
                [b.encode_submit(MAT84, s, mat_key=KEY84)
                 for s in stripes]]
    finally:
        b.stop()
    for o, s in zip(outs, stripes):
        assert (np.asarray(o) == ref_apply(MAT84, s)).all()
    assert b.stats()["flushes"] == 1
    f1, w1 = flush_stats()
    # the flush's host copies are the concat and the transfer, both
    # zero-padded to four stripes; it is NO sync point: the commit sync
    # + its fetch (of the padded parity) ride the encode_wait record
    d_copy = f1.get("host_copy_bytes", 0) - f0.get("host_copy_bytes", 0)
    assert d_copy == 2 * 4 * stripes[0].nbytes
    assert f1.get("sync_points", 0) == f0.get("sync_points", 0)
    assert w1.get("sync_points", 0) - w0.get("sync_points", 0) == 1
    assert w1.get("host_copy_bytes", 0) - w0.get("host_copy_bytes", 0) \
        == 4 * ref_apply(MAT84, stripes[0]).nbytes


# -- pipeline + decode (recovery) paths --------------------------------------

def test_stream_encode_parity():
    batches = [RNG.integers(0, 256, (8, 512), dtype=np.uint8)
               for _ in range(4)]
    refs = [ref_apply(MAT84, x) for x in batches]
    outs = stream_encode(MAT84, iter(batches), mat_key=KEY84)
    assert len(outs) == len(refs)
    for a, r in zip(outs, refs):
        assert (np.asarray(a) == r).all()


def test_decode_chunks_rebuild_hits():
    codec = _codec({"plugin": "jax", "k": "4", "m": "2",
                    "technique": "cauchy_good"})
    data = bytes(RNG.integers(0, 256, 4 * 4096, dtype=np.uint8))
    enc = codec.encode(set(range(6)), data)
    jc = codec._jax_codec
    h0, m0 = jc.rebuild_matrix_hits, jc.rebuild_matrix_misses
    for _ in range(3):  # repeated same-pattern rebuilds reuse the matrix
        dec = codec.decode({0, 1, 2, 3},
                           {i: enc[i] for i in (1, 2, 3, 4, 5)},
                           len(enc[0]))
        out = b"".join(np.asarray(dec[i], np.uint8).tobytes()
                       for i in range(4))
        assert out == data
    assert jc.rebuild_matrix_misses - m0 == 1
    assert jc.rebuild_matrix_hits - h0 == 2


# -- CL8 op-path host-trip audit ---------------------------------------------

AUDIT_TP = '''
import numpy as np
import jax
from ceph_tpu.ops.bitplane import apply_matrix_jax


def leaky_flush(mat, chunks):
    dev = jax.device_put(chunks)
    parity = np.asarray(apply_matrix_jax(mat, dev))
    jax.block_until_ready(parity)
    return parity
'''

AUDIT_TN = '''
import numpy as np
import jax
from ceph_tpu.ops.bitplane import apply_matrix_jax


def deliberate_flush(mat, chunks):
    dev = jax.device_put(chunks)  # noqa: CL8 - the transfer seam
    parity = np.asarray(apply_matrix_jax(mat, dev))  # noqa: CL8 - commit sync
    return parity


def host_only(a, b):
    return np.asarray(a) + np.asarray(b)  # plain host numpy: no finding
'''


def _run_audit(tmp_path: Path, src: str):
    from ceph_tpu.qa.analyzer.core import Config, run

    pkg = tmp_path / "fixpkg"
    (pkg / "osd").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "osd" / "write_batcher.py").write_text(src)
    report = run(Config.discover([str(pkg)]))
    return report


def test_cl8_hosttrip_audit_true_positive(tmp_path):
    report = _run_audit(tmp_path, AUDIT_TP)
    idents = {f.ident for f in report.findings if f.code == "CL8"}
    assert any(i.startswith("hosttrip:leaky_flush:device_put")
               for i in idents), idents
    assert any("asarray(apply_matrix_jax)" in i for i in idents), idents
    assert any("block_until_ready" in i for i in idents), idents


def test_cl8_hosttrip_audit_noqa_suppresses(tmp_path):
    report = _run_audit(tmp_path, AUDIT_TN)
    active = {f.ident for f in report.findings if f.code == "CL8"}
    assert not any(i.startswith("hosttrip:") for i in active), active
    noqa = {f.ident for f in report.noqa if f.code == "CL8"}
    assert any(i.startswith("hosttrip:deliberate_flush") for i in noqa)


def test_cl8_whole_package_audit_clean():
    # the acceptance criterion: zero unsuppressed host-trip findings on
    # the op path of the REAL package
    from ceph_tpu.qa.analyzer.core import Config, run

    repo_pkg = Path(__file__).resolve().parents[1] / "ceph_tpu"
    cfg = Config.discover([str(repo_pkg)])
    cfg.checks = ("CL8",)
    report = run(cfg)
    bad = [f.ident for f in report.findings if f.ident.startswith("hosttrip:")]
    assert not bad, bad
