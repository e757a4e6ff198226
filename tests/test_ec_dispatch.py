"""Kernel-dispatch contract (round-4 verdict item #3): the production
codec path — registry -> plugin=jax -> BitplaneCodec -> apply_matrix_jax —
must reach the fused Pallas kernel on TPU backends, with the XLA bitplane
path as the CPU/fallback lane.  Reference seam:
src/erasure-code/ErasureCodePlugin.h :: ErasureCodePluginRegistry (the
plugin factory) feeding ErasureCodeInterface::encode_chunks.
"""
import numpy as np
import pytest

from ceph_tpu.ops import bitplane
from ceph_tpu.gf.reference_codec import apply_matrix as ref_apply
from ceph_tpu.ops.bitplane import apply_matrix_jax


@pytest.fixture(autouse=True)
def _auto_kernel(monkeypatch):
    monkeypatch.delenv("CEPH_TPU_EC_KERNEL", raising=False)


def _coding(k=4, m=2):
    from ceph_tpu.gf import cauchy_good_coding_matrix

    return np.ascontiguousarray(cauchy_good_coding_matrix(k, m), np.uint8)


def test_auto_mode_uses_xla_on_cpu(monkeypatch):
    called = {"pallas": 0}
    from ceph_tpu.ops import pallas_gf

    real = pallas_gf.apply_matrix_pallas

    def spy(*a, **kw):
        called["pallas"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(pallas_gf, "apply_matrix_pallas", spy)
    mat = _coding()
    data = np.random.default_rng(0).integers(0, 256, (4, 512), np.uint8)
    apply_matrix_jax(mat, data)
    assert called["pallas"] == 0  # CPU backend -> XLA path


def test_forced_pallas_dispatches_and_matches_xla(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_EC_KERNEL", "pallas")
    called = {"pallas": 0}
    from ceph_tpu.ops import pallas_gf

    real = pallas_gf.apply_matrix_pallas

    def spy(*a, **kw):
        called["pallas"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(pallas_gf, "apply_matrix_pallas", spy)
    mat = _coding()
    data = np.random.default_rng(1).integers(0, 256, (4, 768), np.uint8)
    got = np.asarray(apply_matrix_jax(mat, data))
    assert called["pallas"] == 1
    monkeypatch.setenv("CEPH_TPU_EC_KERNEL", "xla")
    want = np.asarray(apply_matrix_jax(mat, data))
    assert called["pallas"] == 1
    np.testing.assert_array_equal(got, want)


def test_tpu_backend_auto_dispatches_to_pallas(monkeypatch):
    """Simulate a TPU backend name: auto mode must pick Pallas (the r4
    gap was exactly this — the registry path stopped at XLA on TPU)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = {}
    from ceph_tpu.ops import pallas_gf

    def fake(mat, chunks, tile=pallas_gf.DEFAULT_TILE, interpret=False):
        seen["interpret"] = interpret
        return ref_apply(mat, np.asarray(chunks))

    monkeypatch.setattr(pallas_gf, "apply_matrix_pallas", fake)
    mat = _coding()
    data = np.random.default_rng(2).integers(0, 256, (4, 256), np.uint8)
    apply_matrix_jax(mat, data)
    assert seen["interpret"] is False  # compiled, not interpreted


def test_auto_mode_pallas_failure_is_loud(monkeypatch):
    """On the TPU backend a Pallas failure raises, every time: nothing
    latches an XLA fallback behind the caller's back."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls = {"pallas": 0}
    from ceph_tpu.ops import pallas_gf

    def boom(*a, **kw):
        calls["pallas"] += 1
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(pallas_gf, "apply_matrix_pallas", boom)
    mat = _coding()
    data = np.random.default_rng(3).integers(0, 256, (4, 256), np.uint8)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="mosaic"):
            apply_matrix_jax(mat, data)
    assert calls["pallas"] == 2


def test_forced_pallas_failure_is_loud(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_EC_KERNEL", "pallas")
    from ceph_tpu.ops import pallas_gf

    def boom(*a, **kw):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(pallas_gf, "apply_matrix_pallas", boom)
    with pytest.raises(RuntimeError, match="mosaic"):
        apply_matrix_jax(_coding(), np.zeros((4, 256), np.uint8))


def test_bad_kernel_env_rejected(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_EC_KERNEL", "cuda")
    with pytest.raises(ValueError, match="CEPH_TPU_EC_KERNEL"):
        apply_matrix_jax(_coding(), np.zeros((4, 256), np.uint8))


def test_registry_codec_reaches_dispatcher(monkeypatch):
    """End-to-end: plugin=jax through the registry encodes through
    apply_matrix_jax (the dispatcher), so the TPU kernel choice applies
    to the OSD/ec_bench path."""
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    hits = {"n": 0}
    real = bitplane.apply_matrix_jax

    def spy(mat, chunks, **kw):
        hits["n"] += 1
        return real(mat, chunks, **kw)

    monkeypatch.setattr(bitplane, "apply_matrix_jax", spy)
    codec = ErasureCodePluginRegistry.instance().factory(
        {"plugin": "jax", "k": "4", "m": "2", "technique": "cauchy_good"}
    )
    data = b"x" * (4 * 128)
    encoded = codec.encode({0, 1, 2, 3, 4, 5}, data)
    assert hits["n"] >= 1
    assert len(encoded) == 6


def test_xor_matrix_pallas_equivalence(monkeypatch):
    """0/1 XOR matrices run bit-exact through the GF Pallas kernel."""
    monkeypatch.setenv("CEPH_TPU_EC_KERNEL", "pallas")
    rng = np.random.default_rng(4)
    B = rng.integers(0, 2, (3, 5), np.uint8)
    rows = rng.integers(0, 256, (5, 384), np.uint8)
    got = np.asarray(bitplane.apply_matrix_jax(B, rows))
    monkeypatch.setenv("CEPH_TPU_EC_KERNEL", "xla")
    want = np.asarray(bitplane.apply_matrix_jax(B, rows))
    np.testing.assert_array_equal(got, want)
