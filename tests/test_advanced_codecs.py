"""SHEC / LRC / CLAY plugin tests.

Models the reference's per-plugin suites (reference:
src/test/erasure-code/TestErasureCodeShec*.cc — exhaustive erasure-pattern
sweeps; TestErasureCodeLrc.cc — layer semantics; TestErasureCodeClay.cc —
sub-chunk repair; SURVEY.md §4 ring 1).
"""
import importlib.util
import itertools
import os

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry, InsufficientChunks, InvalidProfile

REG = ErasureCodePluginRegistry.instance()
SHEC_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "reference", "shec_ref.py")


def _shec_ref():
    """benchmark/reference/shec_ref.py, loaded by its path."""
    spec = importlib.util.spec_from_file_location("shec_ref", SHEC_REF)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _shards(codec, seed=0, sub_mult=1):
    n = codec.get_chunk_count()
    k = codec.get_data_chunk_count()
    L = 64 * sub_mult * getattr(codec, "sub_chunk_count", 1)
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (k, L), dtype=np.uint8)
    parity = np.asarray(codec.encode_chunks(data))
    return {i: data[i] for i in range(k)} | {
        k + i: parity[i] for i in range(n - k)
    }


class TestShec:
    """BASELINE.json config 3: SHEC(6,3,2) single-erasure local decode."""

    def setup_method(self):
        self.codec = REG.factory(
            {"plugin": "shec", "k": "6", "m": "3", "c": "2"}
        )

    def test_profile_validation(self):
        with pytest.raises(InvalidProfile):
            REG.factory({"plugin": "shec", "k": "4", "m": "5", "c": "2"})
        with pytest.raises(InvalidProfile):
            REG.factory({"plugin": "shec", "k": "4", "m": "2", "c": "3"})

    def test_single_erasure_local_recovery(self):
        shards = _shards(self.codec)
        n = 9
        ref = _shec_ref()
        coding = ref.coding_matrix({"k": 6, "m": 3, "c": 2})
        for e in range(n):
            avail = set(range(n)) - {e}
            md = self.codec.minimum_to_decode({e}, avail)
            # locality: the least read set, 3 chunks for a data chunk or
            # a local parity and 6 for the global parity (shingles
            # d0-d5, d0-d2, d3-d5)
            assert len(md) == len(ref.minimum_read({e}, 6, 3, coding)), (
                e, sorted(md))
            assert len(md) == (6 if e == 6 else 3), (e, sorted(md))
            have = {i: shards[i] for i in md}
            out = self.codec.decode_chunks({e}, have)
            np.testing.assert_array_equal(out[e], shards[e])

    def test_all_double_erasures_recoverable(self):
        # c=2: every 2-erasure pattern must decode (exhaustive sweep, the
        # TestErasureCodeShec pattern)
        shards = _shards(self.codec, seed=1)
        for pair in itertools.combinations(range(9), 2):
            avail = {i: v for i, v in shards.items() if i not in pair}
            out = self.codec.decode_chunks(set(pair), avail)
            for e in pair:
                np.testing.assert_array_equal(out[e], shards[e])

    def test_insufficient(self):
        with pytest.raises(InsufficientChunks):
            self.codec.minimum_to_decode({0}, {1, 2})

    def test_wanted_parity_with_erased_window_data(self):
        # review regression: chunks 0 (data, in parity 0's window) and 6
        # (parity 0) both lost; rebuilding parity 6 must first solve data 0
        shards = _shards(self.codec, seed=7)
        avail = {i: v for i, v in shards.items() if i not in (0, 6)}
        md = self.codec.minimum_to_decode({6}, set(avail))
        out = self.codec.decode_chunks({6}, {i: avail[i] for i in md})
        np.testing.assert_array_equal(out[6], shards[6])
        # and the fetch-then-decode flow end to end for both
        out = self.codec.decode_chunks({0, 6}, avail)
        np.testing.assert_array_equal(out[0], shards[0])
        np.testing.assert_array_equal(out[6], shards[6])


class TestLrc:
    PROFILE = {
        "plugin": "lrc",
        "mapping": "DD_DD___",
        "layers": [
            ["DD_DD_cc", {"plugin": "jax", "technique": "cauchy_good"}],
            ["DDc_____", {"plugin": "jax", "technique": "reed_sol_van"}],
            ["___DDc__", {"plugin": "jax", "technique": "reed_sol_van"}],
        ],
    }

    def test_geometry(self):
        codec = REG.factory(self.PROFILE)
        assert codec.get_chunk_count() == 8
        assert codec.get_data_chunk_count() == 4

    def test_local_repair_reads_group_only(self):
        codec = REG.factory(self.PROFILE)
        shards = _shards(codec)
        md = codec.minimum_to_decode({0}, set(range(8)) - {0})
        assert len(md) == 2  # partner data chunk + local XOR parity
        out = codec.decode_chunks({0}, {s: shards[s] for s in md})
        np.testing.assert_array_equal(out[0], shards[0])

    def test_global_layer_covers_group_wipe(self):
        codec = REG.factory(self.PROFILE)
        shards = _shards(codec, seed=2)
        lost = {0, 1}  # whole first local group's data
        avail = {s: v for s, v in shards.items() if s not in lost}
        out = codec.decode_chunks(lost, avail)
        for e in lost:
            np.testing.assert_array_equal(out[e], shards[e])

    def test_kml_sugar(self):
        codec = REG.factory({"plugin": "lrc", "k": "4", "m": "2", "l": "3"})
        assert codec.get_chunk_count() == 8
        shards = _shards(codec, seed=3)
        for e in range(8):
            md = codec.minimum_to_decode({e}, set(range(8)) - {e})
            assert len(md) <= 3  # locality l=3
            out = codec.decode_chunks({e}, {s: shards[s] for s in md})
            np.testing.assert_array_equal(out[e], shards[e])

    def test_minimum_to_decode_uses_global_layer(self):
        # review regression: positions 0,1 lost — local layer can't help
        # alone, but the global MDS layer can; planning must not refuse
        codec = REG.factory(self.PROFILE)
        shards = _shards(codec, seed=8)
        avail = set(range(8)) - {0, 1}
        md = codec.minimum_to_decode({0}, avail)
        out = codec.decode_chunks(
            {0}, {s: shards[s] for s in md}
        )
        np.testing.assert_array_equal(out[0], shards[0])
        md2 = codec.minimum_to_decode({0, 1}, avail)
        out2 = codec.decode_chunks({0, 1}, {s: shards[s] for s in md2})
        np.testing.assert_array_equal(out2[1], shards[1])

    def test_bad_profiles(self):
        with pytest.raises(InvalidProfile):
            REG.factory({"plugin": "lrc", "k": "4", "m": "2", "l": "5"})
        with pytest.raises(InvalidProfile):
            REG.factory({"plugin": "lrc", "mapping": "DD__", "layers": [["DDc", {}]]})


class TestClay:
    """BASELINE.json config 4: CLAY(8,4,d=11) repair bandwidth."""

    def test_profile_validation(self):
        with pytest.raises(InvalidProfile):
            REG.factory({"plugin": "clay", "k": "4", "m": "2", "d": "7"})
        with pytest.raises(InvalidProfile):
            REG.factory({"plugin": "clay", "k": "5", "m": "2", "d": "6"})  # q=2, k+m=7

    def test_sub_chunk_count(self):
        codec = REG.factory({"plugin": "clay", "k": "8", "m": "4", "d": "11"})
        assert codec.get_sub_chunk_count() == 64  # q=4, t=3
        codec = REG.factory({"plugin": "clay", "k": "4", "m": "2", "d": "5"})
        assert codec.get_sub_chunk_count() == 8  # q=2, t=3

    def test_roundtrip_all_double_erasures_small(self):
        codec = REG.factory({"plugin": "clay", "k": "4", "m": "2", "d": "5"})
        shards = _shards(codec, seed=4)
        for pair in itertools.combinations(range(6), 2):
            avail = {i: v for i, v in shards.items() if i not in pair}
            out = codec.decode_chunks(set(pair), avail)
            for e in pair:
                np.testing.assert_array_equal(out[e], shards[e], err_msg=str(pair))

    def test_repair_bandwidth_is_msr_optimal(self):
        codec = REG.factory({"plugin": "clay", "k": "8", "m": "4", "d": "11"})
        Z = codec.sub_chunk_count
        md = codec.minimum_to_decode({3}, set(range(12)) - {3})
        assert len(md) == 11  # reads from d helpers
        total_sub = sum(c for runs in md.values() for _, c in runs)
        naive = codec.k * Z
        assert total_sub / naive == pytest.approx(
            codec.d / (codec.k * codec.q)
        )  # 11/32 = 0.34375

    def test_repair_plan_reads_wanted_available_chunks_in_full(self):
        # A chunk that is wanted AND available must be planned as a full
        # read even when it also serves as a repair helper — the repair
        # sub-chunk ranges alone would under-read it.
        codec = REG.factory({"plugin": "clay", "k": "8", "m": "4", "d": "11"})
        md = codec.minimum_to_decode({3, 5}, set(range(12)) - {3})
        assert md[5] == [(0, -1)]
        # pure helpers still read only the repair planes
        Z = codec.sub_chunk_count
        assert sum(c for _, c in md[0]) == Z // codec.q

    def test_single_repair_every_position(self):
        codec = REG.factory({"plugin": "clay", "k": "8", "m": "4", "d": "11"})
        shards = _shards(codec, seed=5)
        for lost in range(12):
            avail = {i: v for i, v in shards.items() if i != lost}
            out = codec.decode_chunks({lost}, avail)
            np.testing.assert_array_equal(out[lost], shards[lost], err_msg=str(lost))

    def test_quad_erasure_full_decode(self):
        codec = REG.factory({"plugin": "clay", "k": "8", "m": "4", "d": "11"})
        shards = _shards(codec, seed=6)
        lost = {1, 6, 8, 11}
        avail = {i: v for i, v in shards.items() if i not in lost}
        out = codec.decode_chunks(lost, avail)
        for e in lost:
            np.testing.assert_array_equal(out[e], shards[e])

    def test_chunk_size_sub_chunk_aligned(self):
        codec = REG.factory({"plugin": "clay", "k": "8", "m": "4", "d": "11"})
        cs = codec.get_chunk_size(1 << 20)
        assert cs % codec.get_sub_chunk_count() == 0
