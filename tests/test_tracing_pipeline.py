"""Tracer + stream-encode pipeline tests (SURVEY.md §5.1 tracing and
§2.9 pipeline-parallel analog).
"""
import numpy as np

from ceph_tpu.common.tracer import TRACER, device_trace, span, tracepoint
from ceph_tpu.gf.matrix import cauchy_good_coding_matrix
from ceph_tpu.gf.reference_codec import encode_chunks
from ceph_tpu.ops.pipeline import stream_encode


def test_tracer_disabled_is_noop():
    TRACER.clear()
    TRACER.enable(False)
    tracepoint("osd", "op", oid="x")
    with span("osd", "write"):
        pass
    assert TRACER.events() == []


def test_tracer_records_and_bounds():
    TRACER.clear()
    TRACER.enable(True)
    try:
        tracepoint("ec", "encode", nbytes=123)
        with span("crush", "map_batch", n=10):
            pass
        evs = TRACER.events()
        assert any(
            e["subsys"] == "ec" and e["nbytes"] == 123 for e in evs
        )
        crush = TRACER.events("crush")
        assert len(crush) == 1 and crush[0]["dur_ms"] >= 0
    finally:
        TRACER.enable(False)
        TRACER.clear()


def test_device_trace_noop_without_env(monkeypatch):
    monkeypatch.delenv("CEPH_TPU_PROFILE", raising=False)
    with device_trace():
        x = 1
    assert x == 1


def test_stream_encode_matches_single_shot():
    k, m = 4, 2
    coding = cauchy_good_coding_matrix(k, m).astype(np.uint8)
    rng = np.random.default_rng(0)
    batches = [
        rng.integers(0, 256, (k, 8192), dtype=np.uint8) for _ in range(5)
    ]
    outs = stream_encode(coding, batches)
    assert len(outs) == 5
    for b, o in zip(batches, outs):
        np.testing.assert_array_equal(o, encode_chunks(coding, b))


def test_stream_encode_is_truly_streaming():
    """stream_encode consumes its input lazily — a one-shot generator
    works, and at most two batches are ever pulled ahead of the compute
    (the traffic path's bounded host-memory contract)."""
    k, m = 4, 2
    coding = cauchy_good_coding_matrix(k, m).astype(np.uint8)
    rng = np.random.default_rng(1)
    batches = [
        rng.integers(0, 256, (k, 4096), dtype=np.uint8) for _ in range(6)
    ]
    pulled = []

    def gen():
        for i, b in enumerate(batches):
            pulled.append(i)
            yield b

    outs = stream_encode(coding, gen())
    assert pulled == list(range(6))  # fully consumed, in order
    assert len(outs) == 6
    for b, o in zip(batches, outs):
        np.testing.assert_array_equal(o, encode_chunks(coding, b))
    # a list input (the write batcher's burst path) is bit-identical
    outs_list = stream_encode(coding, list(batches))
    for o, oa in zip(outs, outs_list):
        np.testing.assert_array_equal(o, oa)


def test_stream_encode_empty_and_single():
    coding = cauchy_good_coding_matrix(2, 1).astype(np.uint8)
    assert stream_encode(coding, []) == []
    b = np.zeros((2, 256), np.uint8)
    outs = stream_encode(coding, [b])
    assert len(outs) == 1


def test_ec_bench_stream_cli(capsys):
    from ceph_tpu.bench.ec_bench import main

    rc = main([
        "encode", "-P", "jax", "-p", "k=2", "-p", "m=1",
        "-s", "65536", "--stream", "3", "--json",
    ])
    assert rc == 0
    import json

    out = json.loads(capsys.readouterr().out)
    assert out["bytes"] == 65536 * 3 and out["GiB_per_s"] > 0
