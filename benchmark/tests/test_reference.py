"""The codec reference builds the coding matrix a profile's `technique`
states, as jerasure does.  Run: python -m pytest benchmark/tests"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import load_reference  # noqa: E402
from reference import gf_ref  # noqa: E402

# (technique, m) where the program and jerasure agree
AGREE = [("cauchy_good", 3), ("cauchy_good", 4), ("cauchy_orig", 2),
         ("cauchy_orig", 4), ("reed_sol_van", 1)]


def test_reed_sol_van_is_jerasures():
    """jerasure's manual prints reed_sol_van's k=7, m=7 matrix over
    GF(2^8) with a first row and a first column of ones and its second
    row 1 199 210 240 105 121 248."""
    mat = gf_ref.coding_matrix({"technique": "reed_sol_van", "k": 7, "m": 7})
    assert (mat[0] == 1).all() and (mat[:, 0] == 1).all()
    assert mat[1].tolist() == [1, 199, 210, 240, 105, 121, 248]


@pytest.mark.parametrize("technique,m", AGREE)
def test_matrix_matches_program(technique, m):
    from ceph_tpu.ec.plugins.rs import build_coding_matrix

    for k in (2, 4, 6, 8, 10):
        want = build_coding_matrix(technique, k, m)
        got = gf_ref.coding_matrix({"technique": technique, "k": k, "m": m})
        np.testing.assert_array_equal(got, want, err_msg=f"k={k}")


@pytest.mark.xfail(reason="the program's reed_sol_van departs from jerasure "
                          "for m >= 2 (PERF.md, Open questions)")
def test_program_reed_sol_van_m2_is_jerasures():
    from ceph_tpu.ec.plugins.rs import build_coding_matrix

    np.testing.assert_array_equal(
        build_coding_matrix("reed_sol_van", 8, 4),
        gf_ref.coding_matrix({"technique": "reed_sol_van", "k": 8, "m": 4}))


@pytest.mark.parametrize("technique,m", AGREE + [("reed_sol_van", 4)])
def test_decode_recovers_any_erasures(technique, m):
    k = 6
    coding = gf_ref.coding_matrix({"technique": technique, "k": k, "m": m})
    data = np.random.default_rng(7).integers(0, 256, (k, 64), dtype=np.uint8)
    shards = dict(enumerate(np.concatenate([data, gf_ref.apply(coding,
                                                               data)])))
    lost = list(range(k + m))[1::2][:m]
    got = gf_ref.decode(coding, {i: v for i, v in shards.items()
                                 if i not in lost}, lost)
    for s in lost:
        np.testing.assert_array_equal(got[s], shards[s])


def test_every_configured_reference_builds():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), c["file"])) as f:
            cfg = json.load(f)
        if "reference" in cfg:
            ref = load_reference(cfg)
            mat = ref.coding_matrix(cfg["profile"])
            assert mat.shape == (cfg["m"], cfg["k"])
            ref.coding_matrix(dict(cfg["profile"],
                                   technique=cfg["control_technique"]))
