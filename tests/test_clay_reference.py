"""The CLAY plugin against the plain reference benchmark/reference/
clay_ref.py (FAST'18's encode and repair, plane by plane in numpy,
loaded by its path): parity, single-shard repair from the helpers'
repair-plane sub-chunks alone over several stripes, and whole-chunk
repair, bit for bit for every lost shard.

The inner code is cauchy_good at m = 4 and cauchy_orig at m = 2 (the
reference builds cauchy_good only for m > 2: jerasure takes m = 2 from
its cbest tables); the default inner code, reed_sol_van, is checked
against the reference on its own.
"""
import hashlib
import importlib.util
import os

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry, InvalidProfile

REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "reference", "clay_ref.py")
REG = ErasureCodePluginRegistry.instance()
GEOMETRIES = {"clay84_d11": (8, 4, 11, "cauchy_good"),
              "clay42_d5": (4, 2, 5, "cauchy_orig")}
STRIPES, SUB_LEN = 3, 64
CASES = [(g, lost) for g, (k, m, _, _) in GEOMETRIES.items()
         for lost in range(k + m)]


@pytest.fixture(scope="module")
def clay_ref():
    spec = importlib.util.spec_from_file_location("clay_ref", REF)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _profile(geometry):
    k, m, d, technique = GEOMETRIES[geometry]
    return {"plugin": "clay", "k": str(k), "m": str(m), "d": str(d),
            "scalar_mds": "jerasure", "technique": technique}


@pytest.fixture(scope="module")
def encoded(clay_ref):
    """geometry -> (codec, stripe-major shards [S, k+m, L], the
    reference's plane-major chunks [k+m, Z, S*SUB_LEN])."""
    out = {}
    for geometry in GEOMETRIES:
        profile = _profile(geometry)
        codec = REG.factory(profile)
        k, Z = codec.k, codec.sub_chunk_count
        rng = np.random.default_rng(sorted(GEOMETRIES).index(geometry))
        data = rng.integers(0, 256, (STRIPES, k, Z * SUB_LEN),
                            dtype=np.uint8)
        parity = np.stack([codec.encode_chunks(s) for s in data])
        plane_major = data.reshape(STRIPES, k, Z, SUB_LEN).transpose(
            1, 2, 0, 3).reshape(k, Z, STRIPES * SUB_LEN)
        ref = np.concatenate([plane_major,
                              clay_ref.encode(profile, plane_major)])
        out[geometry] = (codec, np.concatenate([data, parity], axis=1), ref)
    return out


def _stripe_major(chunk, Z):
    """A reference [Z, S*SUB_LEN] chunk as S stripes of chunk bytes."""
    return chunk.reshape(Z, STRIPES, SUB_LEN).transpose(1, 0, 2).reshape(-1)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_parity_equals_reference(encoded, geometry):
    codec, shards, ref = encoded[geometry]
    k, Z = codec.k, codec.sub_chunk_count
    for p in range(codec.m):
        np.testing.assert_array_equal(shards[:, k + p].reshape(-1),
                                      _stripe_major(ref[k + p], Z))


@pytest.mark.parametrize("geometry,lost", CASES)
def test_repair_from_sub_chunks_equals_reference(encoded, clay_ref,
                                                 geometry, lost):
    """decode from each helper's repair planes alone, three stripes in
    one call, as minimum_to_decode's ranges read them."""
    codec, shards, ref = encoded[geometry]
    n, Z, L = codec.get_chunk_count(), codec.sub_chunk_count, \
        codec.sub_chunk_count * SUB_LEN
    planes = codec.repair_planes(lost)
    assert planes == clay_ref.repair_planes(_profile(geometry), lost)
    plan = codec.minimum_to_decode({lost}, set(range(n)) - {lost})
    assert all(sum(c for _, c in runs) == Z // codec.q
               for runs in plan.values())
    helpers = {h: np.ascontiguousarray(
        shards[:, h].reshape(STRIPES, Z, SUB_LEN)[:, planes]).reshape(-1)
        for h in plan}
    assert all(len(b) == STRIPES * L // codec.q for b in helpers.values())
    out = codec.decode({lost}, helpers, L)
    want = clay_ref.repair(_profile(geometry), lost,
                           {h: ref[h][planes] for h in helpers})
    np.testing.assert_array_equal(want, ref[lost])
    np.testing.assert_array_equal(out[lost], _stripe_major(want, Z))


@pytest.mark.parametrize("geometry,lost", CASES)
def test_whole_chunk_repair_equals_reference(encoded, geometry, lost):
    codec, shards, ref = encoded[geometry]
    n, Z = codec.get_chunk_count(), codec.sub_chunk_count
    for s in range(STRIPES):
        have = {h: shards[s, h] for h in range(n) if h != lost}
        out = codec.decode({lost}, have, Z * SUB_LEN)
        want = ref[lost].reshape(Z, STRIPES, SUB_LEN)[:, s].reshape(-1)
        np.testing.assert_array_equal(out[lost], want)


def test_cauchy_good_coding_is_the_rs_plugins():
    from ceph_tpu.ec.plugins.rs import build_coding_matrix

    codec = REG.factory(_profile("clay84_d11"))
    np.testing.assert_array_equal(codec.coding,
                                  build_coding_matrix("cauchy_good", 8, 4))


def test_unknown_scalar_mds_is_refused():
    with pytest.raises(InvalidProfile):
        REG.factory(dict(_profile("clay84_d11"), scalar_mds="isa"))


@pytest.mark.parametrize("profile,digest", [
    ({"plugin": "clay", "k": "4", "m": "2"},
     "7af18847629d217c426ca3b565476f8fb43dcf3ca732a1a6e0e83cc4f2763042"),
    ({"plugin": "clay", "k": "8", "m": "4", "d": "11"},
     "8d3e6834673baac703c45c64136f6c8ca0b8fb2fc6d4b837047709a02f752066"),
], ids=["clay42", "clay84_d11"])
def test_default_profile_parity_is_unchanged(clay_ref, profile, digest):
    """The default inner code is jerasure's reed_sol_van: the parity is
    the reference's with that inner code, and its digest is pinned."""
    codec = REG.factory(profile)
    data = np.random.default_rng(2026).integers(
        0, 256, (codec.k, 64 * codec.sub_chunk_count), dtype=np.uint8)
    parity = np.asarray(codec.encode_chunks(data))
    Z = codec.sub_chunk_count
    want = clay_ref.encode(profile, data.reshape(codec.k, Z, 64))
    np.testing.assert_array_equal(parity, want.reshape(codec.m, Z * 64))
    assert hashlib.sha256(parity.tobytes()).hexdigest() == digest


def test_recovery_plan_repairs_through_decode_from_sub_chunks(encoded,
                                                             monkeypatch):
    """The OSD's sub-chunk plan reads 11 x 16 sub-chunks and rebuilds
    through the plugin's repair, one stripe."""
    from ceph_tpu.osd.recovery import RecoveryMixin

    codec, shards, _ = encoded["clay84_d11"]
    lost, n, Z = 5, codec.get_chunk_count(), codec.sub_chunk_count
    stored = {h: shards[0, h].tobytes() for h in range(n)}
    calls = []
    repair = codec._repair
    monkeypatch.setattr(codec, "_repair", lambda *a: calls.append(a[3])
                        or repair(*a))

    class Osd(RecoveryMixin):
        def _fetch_shard_ranges(self, pg, acting, shard, oid, byte_ranges):
            buf = stored[shard]
            return b"".join(buf[o:o + ln] for o, ln in byte_ranges), 7

    plan = codec.minimum_to_decode({lost}, set(range(n)) - {lost})
    helpers = sorted(plan)
    my_shard = helpers[0]
    got = Osd()._plan_subchunk_reads(
        None, codec, list(range(n)), "obj", lost, plan, helpers,
        stored[my_shard], my_shard, 7, None, 4096)
    assert got is not None
    chunk, size, n_helpers, bytes_read = got
    assert chunk == stored[lost]
    assert (size, n_helpers) == (4096, 11)
    assert bytes_read == 11 * 16 * SUB_LEN
    assert calls == [1]  # one repair, of one stripe


@pytest.mark.parametrize("nB,sub_len", [(16, 128), (4, 64)],
                         ids=["kernel", "slices"])
def test_relayouts_equal_a_numpy_transpose(nB, sub_len):
    """The repair's device relayouts, as one Pallas kernel where a block
    is whole uint8 tiles and as slices elsewhere, against numpy."""
    import jax.numpy as jnp

    from ceph_tpu.ec.plugins.clay import _plane_major, _stripe_major

    rng = np.random.default_rng(5)
    stripes, helpers, Z = 3, 11, 4 * nB
    rows = [rng.integers(0, 256, (stripes * nB, sub_len), dtype=np.uint8)
            for _ in range(helpers)]
    want = np.concatenate([
        r.reshape(stripes, nB, sub_len).transpose(1, 0, 2).reshape(
            nB, stripes * sub_len) for r in rows])
    got = _plane_major(tuple(jnp.asarray(r) for r in rows), stripes, True)
    np.testing.assert_array_equal(np.asarray(got), want)

    out = rng.integers(0, 256, (Z, stripes * sub_len), dtype=np.uint8)
    want = out.reshape(Z, stripes, sub_len).transpose(1, 0, 2).reshape(
        stripes * Z, sub_len)
    got = _stripe_major(jnp.asarray(out), stripes, True)
    np.testing.assert_array_equal(np.asarray(got), want)
