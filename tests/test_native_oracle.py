"""Cross-checks: Python GF core vs the independent C++ oracle.

Models the reference's isa<->jerasure parity cross-check
(reference: src/test/erasure-code/TestErasureCodeIsa.cc — "isa and jerasure
reed_sol_van produce identical parity", SURVEY.md §4 ring 1): two independent
implementations of the same constructions must agree bit-for-bit.
"""
import importlib.util
import os

import numpy as np
import pytest

from ceph_tpu import native_oracle as oracle
from ceph_tpu.gf import (
    GF_MUL_TABLE,
    cauchy_good_coding_matrix,
    cauchy_n_ones,
    cauchy_original_coding_matrix,
    invert_matrix,
    vandermonde_coding_matrix,
)
from ceph_tpu.gf.reference_codec import decode_chunks, encode_chunks

pytestmark = pytest.mark.skipif(
    not oracle.available(), reason="native oracle failed to build"
)

KM_GRID = [(2, 1), (3, 2), (4, 2), (6, 3), (8, 4), (10, 4), (12, 3), (20, 7)]


def test_mul_table_identical():
    np.testing.assert_array_equal(oracle.mul_table(), GF_MUL_TABLE)


def test_scalar_ops_spot():
    rng = np.random.default_rng(0)
    for _ in range(500):
        a, b = (int(v) for v in rng.integers(0, 256, 2))
        assert oracle.gf_mul(a, b) == GF_MUL_TABLE[a, b]
    for n in range(256):
        assert oracle.n_ones(n) == cauchy_n_ones(n)


def _gf_ref():
    """benchmark/reference/gf_ref.py (jerasure's constructions, written
    apart from the program), loaded by its path."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "benchmark", "reference", "gf_ref.py")
    spec = importlib.util.spec_from_file_location("gf_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("k,m", KM_GRID)
def test_vandermonde_identical(k, m):
    """The oracle, the program and the plain reference build jerasure's
    reed_sol_van alike."""
    want = vandermonde_coding_matrix(k, m).astype(np.uint8)
    np.testing.assert_array_equal(oracle.vandermonde(k, m), want)
    np.testing.assert_array_equal(_gf_ref().reed_sol_van(k, m), want)


@pytest.mark.parametrize("k,m", KM_GRID)
def test_cauchy_identical(k, m):
    np.testing.assert_array_equal(
        oracle.cauchy_original(k, m),
        cauchy_original_coding_matrix(k, m).astype(np.uint8),
    )
    np.testing.assert_array_equal(
        oracle.cauchy_good(k, m), cauchy_good_coding_matrix(k, m).astype(np.uint8)
    )


def test_invert_identical():
    rng = np.random.default_rng(1)
    done = 0
    while done < 10:
        n = int(rng.integers(2, 10))
        mat = rng.integers(0, 256, (n, n)).astype(np.uint8)
        try:
            py = invert_matrix(mat)
        except np.linalg.LinAlgError:
            with pytest.raises(np.linalg.LinAlgError):
                oracle.invert(mat)
            continue
        np.testing.assert_array_equal(oracle.invert(mat), py.astype(np.uint8))
        done += 1


@pytest.mark.parametrize("k,m", [(2, 1), (8, 4), (6, 3)])
@pytest.mark.parametrize("fast", [False, True])
def test_encode_parity_identical(k, m, fast):
    coding = vandermonde_coding_matrix(k, m)
    rng = np.random.default_rng(k + m)
    # odd length exercises the SIMD tail path
    data = rng.integers(0, 256, (k, 4096 + 13), dtype=np.uint8)
    np.testing.assert_array_equal(
        oracle.encode(coding, data, fast=fast), encode_chunks(coding, data)
    )


def test_fast_path_runs_simd_or_reports():
    # gfo_apply_fast returns 2 for AVX2, 1 for SSSE3, 0 for scalar
    coding = vandermonde_coding_matrix(4, 2)
    data = np.zeros((4, 64), dtype=np.uint8)
    out = np.empty((2, 64), dtype=np.uint8)
    rc = oracle._lib().gfo_apply_fast(
        np.ascontiguousarray(coding, dtype=np.uint8).reshape(-1), 2, 4,
        data.reshape(-1), 64, out.reshape(-1),
    )
    assert rc in (0, 1, 2)


@pytest.mark.parametrize("k,m", [(8, 4), (6, 3)])
def test_decode_identical(k, m):
    coding = cauchy_good_coding_matrix(k, m)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (k, 1024), dtype=np.uint8)
    parity = encode_chunks(coding, data)
    shards = np.vstack([data, parity])
    for trial in range(10):
        erased = rng.choice(k + m, size=m, replace=False)
        avail = sorted(set(range(k + m)) - set(int(e) for e in erased))
        got = oracle.decode(coding, k, avail, shards[avail[:k]])
        np.testing.assert_array_equal(got, data)
        py = decode_chunks(
            coding, k, {r: shards[r] for r in avail}, want=list(range(k))
        )
        for i in range(k):
            np.testing.assert_array_equal(py[i], data[i])
