"""The trace reduction and the roofline arithmetic, on a synthetic xplane
trace with hand-placed events.  Run: python -m pytest benchmark/tests"""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import roofline  # noqa: E402
import trace_reduce  # noqa: E402

# one TPU plane: ops at [10, 30) and [20, 40) us (overlapping: busy 30 us),
# a GF kernel [50, 60) us and a straw2 kernel [70, 75) us; host spans: the
# window [0, 100) us, a "fetch" span [40, 50) us and a "prepare" span
# [75, 100) us.  Device idle in the window: [0,10) none, [40,50) fetch,
# [60,70) none, [75,100) prepare.
OPS = [
    (1, "%fusion.1 = u8[8,64] fusion(u8[8,64] %x)", 10, 20),
    (2, "%copy.3 = u8[8,64] copy(u8[8,64] %y)", 20, 20),
    (3, "%_apply_grouped.1 = u8[32,1024] custom-call(s8[256,512] %b)", 50, 10),
    (4, "%straw2_scores_pallas.2 = (s32[64,128]) custom-call(s32[64,1] %x)",
     70, 5),
]
SPANS = [
    (11, "bench:window", 0, 100),
    (12, "bench:fetch", 40, 10),
    (13, "bench:prepare", 75, 25),
    (14, "not_ours", 0, 100),
]


def _text_proto(ops=OPS) -> str:
    def plane(pid, name, line, events):
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for i, n, _, _ in events)
        evs = "".join(
            f"events {{ metadata_id: {i} offset_ps: {s * 10**6} "
            f"duration_ps: {d * 10**6} }}\n" for i, _, s, d in events)
        return (f'planes {{ id: {pid} name: "{name}"\n'
                f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n'
                f"{meta}}}\n")

    return (plane(1, "/device:TPU:0", "XLA Ops", ops)
            + plane(2, "/host:CPU", "python", SPANS))


@pytest.fixture
def trace():
    from jax.profiler import ProfileData

    return trace_reduce.from_profile(ProfileData.from_text_proto(_text_proto()))


def test_window_and_busy_is_a_union(trace):
    assert trace.window_s() == pytest.approx(100e-6)
    # [10,40) + [50,60) + [70,75): overlapping ops count once
    assert trace.busy_s() == pytest.approx(45e-6)


def test_kernel_time_by_name(trace):
    secs, names = trace.kernel(r"_apply_grouped[.\d]* = .*custom-call\(")
    assert secs == pytest.approx(10e-6)
    assert names == ["_apply_grouped.1"]
    secs, _ = trace.kernel(r"straw2_scores_pallas[.\d]* = .*custom-call\(")
    assert secs == pytest.approx(5e-6)


def test_missing_kernel_fails(trace):
    with pytest.raises(trace_reduce.KernelMissing):
        trace.kernel(r"no_such_kernel")


def test_top_ops_and_idle_gaps(trace):
    top = trace.top_ops(2)
    assert top[0][0] in ("fusion.1", "copy.3") and top[0][1] == pytest.approx(20e-6)
    gaps = dict(trace.idle_gaps())
    assert gaps["bench:prepare"] == pytest.approx(25e-6)
    assert gaps["bench:fetch"] == pytest.approx(10e-6)
    assert gaps["(none)"] == pytest.approx(20e-6)
    assert sum(gaps.values()) == pytest.approx(100e-6 - trace.busy_s())


def test_readers_on_the_trace(trace):
    from run import LayerContext, metric_reader

    ctx = LayerContext(trace, {"gf_applies": [(4, 8, 1000, 2)],
                               "pgs_mapped": 50}, "TPU v5 lite",
                       lambda msg: None)
    need = 2 * 12 * 1000 / 819e9
    got = metric_reader("gf_kernel_roofline").read(
        ctx, {"name": "gf_kernel_roofline"})
    assert got == pytest.approx(100 * need / 10e-6)
    assert metric_reader("device_idle_pct.codec").read(
        ctx, {"name": "device_idle_pct.codec"}) == pytest.approx(55.0)
    assert metric_reader("straw2_ns_per_map.crush").read(
        ctx, {"name": "straw2_ns_per_map.crush"}) == pytest.approx(100.0)


def test_reader_returns_nothing_without_its_kernel():
    from jax.profiler import ProfileData

    from run import LayerContext, metric_reader

    tr = trace_reduce.from_profile(
        ProfileData.from_text_proto(_text_proto(OPS[:2])))
    ctx = LayerContext(tr, {"gf_applies": [(4, 8, 1000, 1)]}, "TPU v5 lite",
                       lambda msg: None)
    assert metric_reader("gf_kernel_roofline").read(
        ctx, {"name": "gf_kernel_roofline"}) is None


def test_gf_apply_bytes_and_ops_by_hand():
    # RS(8,4) encode of 64 x 1 MiB objects: 8 + 4 rows of 8 MiB
    assert roofline.gf_apply_bytes(4, 8, 8 << 20) == 12 * (8 << 20)
    # decode: 8 x 8 apply, then 2 parity rows re-encoded
    assert roofline.gf_apply_bytes(8, 8, 1 << 20) == 16 << 20
    assert roofline.gf_apply_bytes(2, 8, 1 << 20) == 10 << 20
    # 32 x 64 bit pairs per byte column, a multiply and an add each
    assert roofline.gf_apply_ops(4, 8, 1) == 2 * 32 * 64
    # bytes bind for the encode: 100.6 MB / 819 GB/s > 34.4 GOP / 393 TOP/s
    t = roofline.gf_apply_min_s(4, 8, 8 << 20, "TPU v5 lite")
    assert t == pytest.approx(12 * (8 << 20) / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
