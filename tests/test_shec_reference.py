"""The SHEC plugin against the plain reference benchmark/reference/
shec_ref.py (upstream's shingled reed_sol_van construction, written
apart from the program and loaded by its path): the coding matrix and
parity for both techniques, the least read set and the bytes of every
single erasure, every double erasure of (6,3,2), the decode's rows path
through the Pallas kernel (interpret mode) at a row length that is not
a power of two, and the program's reed_sol_van against jerasure's.
"""
import importlib.util
import itertools
import os

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry, InvalidProfile

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "reference")
REG = ErasureCodePluginRegistry.instance()
GEOMETRIES = [(6, 3, 2), (4, 3, 2), (8, 4, 3)]
TECHNIQUES = ["multiple", "single"]
PROFILES = [(t, g) for t in TECHNIQUES for g in GEOMETRIES]
PROFILE_IDS = [f"{t}-{k}{m}{c}" for t, (k, m, c) in PROFILES]
L = 192


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


shec_ref = _load("shec_ref")
gf_ref = _load("gf_ref")


def _profile(technique, geometry):
    k, m, c = geometry
    return {"plugin": "shec", "k": str(k), "m": str(m), "c": str(c),
            "technique": technique}


def _shards(codec, seed, length=L):
    data = np.random.default_rng(seed).integers(
        0, 256, (codec.k, length), dtype=np.uint8)
    return dict(enumerate(np.concatenate(
        [data, np.asarray(codec.encode_chunks(data))])))


@pytest.mark.parametrize("technique,geometry", PROFILES, ids=PROFILE_IDS)
def test_coding_matrix_and_parity_equal_reference(technique, geometry):
    profile = _profile(technique, geometry)
    codec = REG.factory(profile)
    want = shec_ref.coding_matrix(profile)
    np.testing.assert_array_equal(codec.coding.astype(np.uint8), want)
    shards = _shards(codec, seed=sum(geometry))
    data = np.stack([shards[i] for i in range(codec.k)])
    np.testing.assert_array_equal(
        np.stack([shards[codec.k + p] for p in range(codec.m)]),
        shec_ref.apply(want, data))


def test_multiple_is_the_default_and_its_shingles():
    """(6,3,2) multiple splits the parities into (m1, c1) = (1, 1) and
    (2, 1): parity 0 covers d0-d5, parity 1 d0-d2, parity 2 d3-d5."""
    codec = REG.factory({"plugin": "shec", "k": "6", "m": "3", "c": "2"})
    assert codec.technique == "multiple"
    assert [sorted(codec._window(p)) for p in range(3)] == \
        [[0, 1, 2, 3, 4, 5], [0, 1, 2], [3, 4, 5]]
    single = REG.factory(_profile("single", (6, 3, 2)))
    assert [sorted(single._window(p)) for p in range(3)] == \
        [[0, 1, 2, 3], [2, 3, 4, 5], [0, 1, 4, 5]]


def test_unknown_technique_is_refused():
    with pytest.raises(InvalidProfile):
        REG.factory(_profile("reed_sol_van", (6, 3, 2)))


@pytest.mark.parametrize("technique,geometry", PROFILES, ids=PROFILE_IDS)
def test_single_erasure_reads_the_least_and_decodes(technique, geometry):
    """Every single erasure: minimum_to_decode names as many chunks as
    the reference's brute-force least read set, and decode from exactly
    those chunks gives the lost chunk's bytes."""
    profile = _profile(technique, geometry)
    codec = REG.factory(profile)
    coding = shec_ref.coding_matrix(profile)
    shards = _shards(codec, seed=3)
    n = codec.k + codec.m
    for lost in range(n):
        plan = codec.minimum_to_decode({lost}, set(range(n)) - {lost})
        least = shec_ref.minimum_read({lost}, codec.k, codec.m, coding)
        assert len(plan) == len(least), (lost, sorted(plan), least)
        have = {c: shards[c] for c in plan}
        out = codec.decode({lost}, have, L)
        np.testing.assert_array_equal(out[lost], shards[lost])
        np.testing.assert_array_equal(
            shec_ref.rebuild({lost}, have, coding)[lost], shards[lost])


def test_632_read_sets():
    """(6,3,2) multiple: 3 chunks for 8 of the 9 shards, 6 for the
    global parity."""
    codec = REG.factory(_profile("multiple", (6, 3, 2)))
    sizes = [len(codec.minimum_to_decode({s}, set(range(9)) - {s}))
             for s in range(9)]
    assert sizes == [3, 3, 3, 3, 3, 3, 6, 3, 3]


@pytest.mark.parametrize("technique", TECHNIQUES)
def test_every_double_erasure_decodes(technique):
    profile = _profile(technique, (6, 3, 2))
    codec = REG.factory(profile)
    coding = shec_ref.coding_matrix(profile)
    shards = _shards(codec, seed=11)
    for pair in itertools.combinations(range(9), 2):
        avail = {i: v for i, v in shards.items() if i not in pair}
        plan = codec.minimum_to_decode(set(pair), set(avail))
        assert len(plan) == len(shec_ref.minimum_read(pair, 6, 3, coding))
        out = codec.decode(set(pair), {c: avail[c] for c in plan}, L)
        ref = shec_ref.rebuild(pair, avail, coding)
        for e in pair:
            np.testing.assert_array_equal(out[e], shards[e])
            np.testing.assert_array_equal(ref[e], shards[e])


@pytest.mark.parametrize("lost", [2, 6, 8])
def test_rows_path_decode_equals_reference(lost, monkeypatch):
    """The decode's rows path through the Pallas kernel (interpret
    mode): the read set's rows committed unstacked, at a row length of
    3 x 513 x 64 B (not a power of two, padded on the device), equals
    the numpy reference's rebuild."""
    monkeypatch.setenv("CEPH_TPU_EC_KERNEL", "pallas")
    profile = _profile("multiple", (6, 3, 2))
    codec = REG.factory(profile)
    length = 3 * 513 * 64
    data = np.random.default_rng(lost).integers(0, 256, (6, length),
                                                dtype=np.uint8)
    coding = shec_ref.coding_matrix(profile)
    shards = dict(enumerate(np.concatenate(
        [data, shec_ref.apply(coding, data)])))
    plan = codec.minimum_to_decode({lost}, set(range(9)) - {lost})
    have = {c: shards[c] for c in plan}
    out = codec.decode({lost}, have, length)
    want = shec_ref.rebuild({lost}, have, coding)[lost]
    assert out[lost].dtype == np.uint8 and out[lost].shape == (length,)
    np.testing.assert_array_equal(out[lost], want)
    np.testing.assert_array_equal(want, shards[lost])


@pytest.mark.parametrize("k,m", [(2, 2), (4, 2), (6, 3), (8, 4), (10, 4),
                                 (7, 7), (12, 5)])
def test_reed_sol_van_is_jerasures(k, m):
    from ceph_tpu.ec.plugins.rs import build_coding_matrix

    np.testing.assert_array_equal(build_coding_matrix("reed_sol_van", k, m),
                                  gf_ref.reed_sol_van(k, m))
