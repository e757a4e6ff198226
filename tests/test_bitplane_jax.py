"""JAX bitplane codec vs the C++ oracle and numpy reference — ring-1 tests
modeling the reference's cross-plugin parity checks (reference:
src/test/erasure-code/TestErasureCodeIsa.cc cross-check vs jerasure).
"""
import numpy as np
import pytest

from ceph_tpu import native_oracle as oracle
from ceph_tpu.gf import (
    cauchy_good_coding_matrix,
    vandermonde_coding_matrix,
)
from ceph_tpu.gf.reference_codec import encode_chunks
from ceph_tpu.ops import BitplaneCodec, apply_matrix_jax, pack_bitplanes, unpack_bitplanes

ORACLE = oracle.available()


def test_pack_unpack_roundtrip():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 256, (3, 257), dtype=np.uint8))
    np.testing.assert_array_equal(np.asarray(pack_bitplanes(unpack_bitplanes(x))), x)


@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 4), (6, 3), (10, 4)])
@pytest.mark.parametrize("technique", ["reed_sol_van", "cauchy_good"])
def test_encode_bit_exact(k, m, technique):
    mk = vandermonde_coding_matrix if technique == "reed_sol_van" else cauchy_good_coding_matrix
    coding = mk(k, m)
    rng = np.random.default_rng(k * 31 + m)
    # deliberately awkward length (not multiple of 128 lanes)
    data = rng.integers(0, 256, (k, 4096 + 77), dtype=np.uint8)
    got = np.asarray(BitplaneCodec(coding).encode(data))
    np.testing.assert_array_equal(got, encode_chunks(coding, data))
    if ORACLE:
        np.testing.assert_array_equal(got, oracle.encode(coding, data, fast=True))


@pytest.mark.parametrize("k,m", [(8, 4), (6, 3)])
def test_decode_bit_exact_random_patterns(k, m):
    coding = cauchy_good_coding_matrix(k, m)
    codec = BitplaneCodec(coding)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (k, 2048), dtype=np.uint8)
    parity = np.asarray(codec.encode(data))
    shards = np.vstack([data, parity])
    for _ in range(12):
        erased = set(int(e) for e in rng.choice(k + m, size=m, replace=False))
        avail = sorted(set(range(k + m)) - erased)
        got = np.asarray(codec.decode(avail, shards[avail]))
        np.testing.assert_array_equal(got, data)
        if ORACLE:
            np.testing.assert_array_equal(
                got, oracle.decode(coding, k, avail, shards[avail])
            )


def test_reconstruct_parity_shards():
    k, m = 8, 4
    codec = BitplaneCodec(vandermonde_coding_matrix(k, m))
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, (k, 512), dtype=np.uint8)
    parity = np.asarray(codec.encode(data))
    shards = np.vstack([data, parity])
    # lose data shard 3 and parity shard k+1; rebuild both from the rest
    avail = [i for i in range(k + m) if i not in (3, k + 1)]
    rebuilt = np.asarray(codec.reconstruct(avail, shards[avail], [3, k + 1]))
    assert rebuilt.shape == (2, 512)
    np.testing.assert_array_equal(rebuilt[0], data[3])
    np.testing.assert_array_equal(rebuilt[1], parity[1])
    assert (codec.rebuild_matrix_hits, codec.rebuild_matrix_misses) == (0, 1)
    # the same pattern again is a hit; other wanted rows are a new matrix
    codec.reconstruct(avail, shards[avail], [3, k + 1])
    only = np.asarray(codec.reconstruct(avail, shards[avail], [k + 1]))
    np.testing.assert_array_equal(only, parity[1:2])
    assert (codec.rebuild_matrix_hits, codec.rebuild_matrix_misses) == (1, 2)


def _rs84_pair():
    from ceph_tpu.ec.plugins.rs import RSCodec

    profile = {"k": "8", "m": "4", "technique": "cauchy_good"}
    return RSCodec(profile, backend="jax"), RSCodec(profile, backend="numpy")


@pytest.mark.parametrize("e", [1, 2, 3, 4])
def test_rs_decode_every_pattern_matches_numpy(e):
    """Every pattern of e lost shards of RS(8,4): the device codec's
    one-apply rebuild equals the numpy referee byte for byte."""
    from itertools import combinations

    dev, ref = _rs84_pair()
    rng = np.random.default_rng(40 + e)
    data = rng.integers(0, 256, (8, 512), dtype=np.uint8)
    shards = np.vstack([data, ref.encode_chunks(data)])
    for lost in combinations(range(12), e):
        have = {i: shards[i] for i in range(12) if i not in lost}
        got = dev.decode(set(lost), have, 512)
        want = ref.decode(set(lost), have, 512)
        assert sorted(got) == sorted(want) == list(lost)
        for i in lost:
            assert np.asarray(got[i]).tobytes() == want[i].tobytes() \
                == shards[i].tobytes()


def test_rs_decode_wanted_subset_of_lost():
    """Asked for two of four lost shards, the codec rebuilds and returns
    those two, through a two-row rebuild matrix."""
    dev, ref = _rs84_pair()
    rng = np.random.default_rng(44)
    data = rng.integers(0, 256, (8, 512), dtype=np.uint8)
    shards = np.vstack([data, ref.encode_chunks(data)])
    lost = (2, 6, 9, 10)
    have = {i: shards[i] for i in range(12) if i not in lost}
    got = dev.decode({6, 10}, have, 512)
    assert sorted(got) == [6, 10]
    for i in got:
        assert got[i].tobytes() == shards[i].tobytes()
    use = sorted(have)
    rows = dev._jax_codec.reconstruct(use, np.stack([have[i] for i in use]),
                                      [6, 10])
    assert rows.shape == (2, 512)
    assert (dev._jax_codec.rebuild_matrix_hits,
            dev._jax_codec.rebuild_matrix_misses) == (1, 1)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("lost", [(1, 5, 9, 11), (0,), (8, 9, 10, 11)],
                         ids=lambda t: "-".join(map(str, t)))
def test_reconstruct_rows_from_two_arrays(monkeypatch, kernel, lost):
    """Survivor rows taken as they are from two arrays, the data and the
    parity, and passed unstacked: reconstruct rebuilds the lost shards
    and decode the data, bit-exact with the numpy referee (both kernels;
    the Pallas one in interpret mode)."""
    monkeypatch.setenv("CEPH_TPU_EC_KERNEL", kernel)
    dev, ref = _rs84_pair()
    rng = np.random.default_rng(46 + len(lost))
    L = 3000  # not a multiple of any tile: the pad is the device's
    data = rng.integers(0, 256, (8, L), dtype=np.uint8)
    parity = ref.encode_chunks(data)
    have = {i: (data[i] if i < 8 else parity[i - 8])
            for i in range(12) if i not in lost}
    use = sorted(have)[:8]
    rows = [have[i] for i in use]
    want = ref.decode(set(lost), have, L)
    got = np.asarray(dev._jax_codec.reconstruct(use, rows, list(lost)))
    for w, i in zip(got, lost):
        assert w.tobytes() == want[i].tobytes()
    np.testing.assert_array_equal(
        np.asarray(dev._jax_codec.decode(use, rows)), data)


def test_decode_matrix_cache_hit():
    codec = BitplaneCodec(vandermonde_coding_matrix(4, 2))
    a = codec.decode_matrix((1, 2, 3, 4))
    b = codec.decode_matrix((1, 2, 3, 4))
    assert a is b  # cached per erasure pattern


def test_apply_matrix_identity_passthrough():
    data = np.arange(512, dtype=np.uint8).reshape(4, 128)
    out = np.asarray(apply_matrix_jax(np.eye(4, dtype=np.uint8), data))
    np.testing.assert_array_equal(out, data)


def test_errors():
    codec = BitplaneCodec(vandermonde_coding_matrix(4, 2))
    with pytest.raises(ValueError):
        codec.encode(np.zeros((3, 16), np.uint8))
    with pytest.raises(ValueError):
        codec.decode([0, 1, 2], np.zeros((3, 16), np.uint8))
