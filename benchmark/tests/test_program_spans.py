"""The program's stage spans (program_spans.py) and the readers built on
them (stage_ms, idle_named_pct), on a synthetic xplane trace with
hand-placed events.  Run: python -m pytest benchmark/tests"""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import program_spans  # noqa: E402
import trace_reduce  # noqa: E402

# one TPU plane: ops at [10, 20) and [60, 65) us.  Host thread 0: the
# window [0, 100), a call [4, 96), and in it the program's encode
# [8, 90) holding h2d [9, 12), dispatch [12, 14), wait [14, 30) and
# d2h [30, 80); a span "ecx" [90, 92) that no "ec" prefix covers.
# Host thread 1 (same line name): a d2h [50, 70) under nothing, which
# thread 0's encode must not subtract.  Device idle: [0, 10) in the
# call only, [20, 60) in d2h, [65, 100) in the encode (its midpoint).
OPS = [
    (1, "%fusion.1 = u8[8,64] fusion(u8[8,64] %x)", 10, 10),
    (2, "%_apply_grouped.1 = u8[32,1024] custom-call(s8[256,512] %b)",
     60, 5),
]
THREAD0 = [
    (11, "bench:window", 0, 100),
    (12, "bench:call", 4, 92),
    (13, "cephtrace:ec.encode", 8, 82),
    (14, "cephtrace:gf.h2d", 9, 3),
    (15, "cephtrace:gf.dispatch", 12, 2),
    (16, "cephtrace:gf.wait", 14, 16),
    (17, "cephtrace:gf.d2h#trace=abc", 30, 50),
    (18, "cephtrace:ecx", 90, 2),
]
THREAD1 = [(21, "cephtrace:gf.d2h", 50, 20)]


def _text_proto(threads=(THREAD0, THREAD1)) -> str:
    def events(evs):
        return "".join(
            f"events {{ metadata_id: {i} offset_ps: {s * 10**6} "
            f"duration_ps: {d * 10**6} }}\n" for i, _, s, d in evs)

    def meta(evs):
        return "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for i, n, _, _ in evs)

    device = (f'planes {{ id: 1 name: "/device:TPU:0"\n'
              f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n'
              f"{events(OPS)}}}\n{meta(OPS)}}}\n")
    lines = "".join(f'lines {{ id: {k + 1} name: "python" timestamp_ns: 0\n'
                    f"{events(evs)}}}\n" for k, evs in enumerate(threads))
    host = (f'planes {{ id: 2 name: "/host:CPU"\n{lines}'
            f"{meta([e for evs in threads for e in evs])}}}\n")
    return device + host


def _profile(text=None):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text or _text_proto())


@pytest.fixture
def trace():
    return trace_reduce.from_profile(_profile())


@pytest.fixture
def spans(trace):
    return program_spans.from_profile(_profile(), trace.window)


def _written(tmp_path, text) -> str:
    """`text` as an xplane file where run.py puts a cell's profile;
    the glob that finds it."""
    from jax.profiler import ProfileData

    path = tmp_path / "cell" / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path / "*" / "plugins" / "profile" / "*" / "*.xplane.pb")


def _ctx(trace, calls=2):
    from run import LayerContext

    return LayerContext(trace, {"calls": calls}, "TPU v5 lite",
                        lambda msg: None)


def _by_name(spans):
    out: dict[str, float] = {}
    for (name, _, _, _), ns in zip(spans, program_spans.self_ns(spans)):
        out[name] = out.get(name, 0.0) + ns
    return out


def test_spans_with_their_threads(spans):
    assert sorted((n, s / 1e3, e / 1e3) for n, _, s, e in spans) == [
        ("ec.encode", 8, 90), ("ecx", 90, 92), ("gf.d2h", 30, 80),
        ("gf.d2h", 50, 70), ("gf.dispatch", 12, 14), ("gf.h2d", 9, 12),
        ("gf.wait", 14, 30)]
    # the two threads share a line name and stay apart
    assert len({t for n, t, _, _ in spans if n == "gf.d2h"}) == 2


def test_self_time_subtracts_children_on_the_same_thread(spans):
    got = _by_name(spans)
    # 82 us less h2d, dispatch, wait and d2h: 82 - 3 - 2 - 16 - 50
    assert got["ec.encode"] == pytest.approx(11e3)
    # both d2h spans keep all of their time: thread 1's is nested in
    # nothing, and it does not count against thread 0's encode
    assert got["gf.d2h"] == pytest.approx(70e3)
    # every span's self time adds up to the union of the spans, per thread
    assert sum(got.values()) == pytest.approx(84e3 + 20e3)


def test_stage_prefix_rule():
    assert program_spans.stage_of("gf.h2d", "gf.h2d")
    assert program_spans.stage_of("gf.h2d", "gf")
    assert program_spans.stage_of("ec.decode_matrix", "ec")
    assert not program_spans.stage_of("ecx", "ec")
    assert not program_spans.stage_of("gf.h2d_more", "gf.h2d")


def test_idle_is_charged_to_program_stages(trace, spans):
    gaps = dict(program_spans.attribution(trace, spans).idle_gaps(100))
    assert gaps == pytest.approx({"bench:call": 10e-6,
                                  "cephtrace:gf.d2h": 40e-6,
                                  "cephtrace:ec.encode": 35e-6})
    # the benchmark's own reduction still sees only its spans
    assert dict(trace.idle_gaps()) == pytest.approx({"bench:call": 85e-6})


def test_readers(trace, monkeypatch, tmp_path):
    from run import metric_reader

    monkeypatch.setattr(program_spans, "PROFILES",
                        _written(tmp_path, _text_proto()))
    ctx = _ctx(trace)

    def read(name):
        return metric_reader(name).read(ctx, {"name": name})

    # self time in ms per call, over 2 calls
    assert read("stage_ms.gf.h2d") == pytest.approx(3e-3 / 2)
    assert read("stage_ms.gf.d2h") == pytest.approx(70e-3 / 2)
    assert read("stage_ms.gf") == pytest.approx((3 + 2 + 16 + 70) * 1e-3 / 2)
    assert read("stage_ms.ec") == pytest.approx(11e-3 / 2)
    assert read("idle_named_pct.codec") == pytest.approx(100 * 75 / 85)
    # a stage the program never entered reads nothing
    assert read("stage_ms.osdmap.post_pass") is None


def test_a_profile_of_another_window_yields_none(trace, monkeypatch,
                                                 tmp_path):
    from run import metric_reader

    other = _text_proto(([(11, "bench:window", 0, 99)] + THREAD0[1:],
                         THREAD1))
    assert program_spans.from_profile(_profile(other), trace.window) is None
    pattern = _written(tmp_path, other)
    assert program_spans.load(_ctx(trace), pattern) is None
    monkeypatch.setattr(program_spans, "PROFILES", pattern)
    assert metric_reader("idle_named_pct.codec").read(
        _ctx(trace), {"name": "idle_named_pct.codec"}) is None


def test_a_program_without_stages_reads_nothing(monkeypatch, tmp_path):
    """The parent of this change: no cephtrace span, no metric."""
    from run import metric_reader

    text = _text_proto(([e for e in THREAD0
                         if not e[1].startswith("cephtrace:")],))
    tr = trace_reduce.from_profile(_profile(text))
    monkeypatch.setattr(program_spans, "PROFILES", _written(tmp_path, text))
    for name in ("stage_ms.gf.d2h", "stage_ms.ec", "idle_named_pct.codec"):
        assert metric_reader(name).read(_ctx(tr), {"name": name}) is None
