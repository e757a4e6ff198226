"""The device paths' host stages (common/tracer.py `stage`,
`DEVICE_STAGES`): the `cephtrace:<stage>` spans the codec and placement
paths write into a JAX profiler session, how they nest, and that they
change no output byte.

Each case runs the path under `jax.profiler` into a temporary directory
and reads the spans back from the xplane file, as the benchmark's
readers do (benchmark/program_spans.py).
"""
import glob
import os

import numpy as np
import pytest

from ceph_tpu.common.tracer import DEVICE_STAGES, TRACER, stage

PREFIX = "cephtrace:"
DOCS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "docs", "tracing.md")


def _profiled(log_dir, fn):
    """fn() under a profiler session -> (its result, [(stage, label,
    thread, start_ns, end_ns)] of the cephtrace spans, by start)."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    label = e.name[len(PREFIX):]
                    spans.append((label.split("#", 1)[0], label,
                                  f"{plane.name}/{i}", e.start_ns, e.end_ns))
    return out, sorted(spans, key=lambda s: (s[3], -s[4]))


def _inside(span, outer) -> bool:
    return (span[2] == outer[2] and outer[3] <= span[3]
            and span[4] <= outer[4])


def _assert_nested(spans, inner_prefix, outer_names):
    inner = [s for s in spans if s[0].startswith(inner_prefix)]
    outer = [s for s in spans if s[0] in outer_names]
    assert inner
    for s in inner:
        assert any(_inside(s, o) for o in outer if o is not s), s


def _codec(backend="jax"):
    from ceph_tpu.ec.plugins.rs import RSCodec

    return RSCodec({"k": "8", "m": "4", "technique": "cauchy_good"},
                   backend=backend)


@pytest.fixture(scope="module")
def shards():
    rng = np.random.default_rng(23)
    data = rng.integers(0, 256, size=(8, 4096), dtype=np.uint8)
    parity = _codec("numpy").encode_chunks(data)
    return data, parity


def test_encode_stages_and_bytes(tmp_path, shards):
    data, parity = shards
    codec = _codec()
    out, spans = _profiled(tmp_path, lambda: codec.encode_chunks(data))
    assert out.tobytes() == parity.tobytes()
    names = {s[0] for s in spans}
    assert names == {"ec.encode", "gf.h2d", "gf.dispatch", "gf.wait",
                     "gf.d2h"}
    assert names <= set(DEVICE_STAGES)
    _assert_nested(spans, "gf.", {"ec.encode"})


def test_decode_stages_and_bytes(tmp_path, shards):
    """Four erasures, two of them parity: one apply over the survivors
    rebuilds exactly the four lost shards, through gf.*.  The survivors
    are not stacked on the host: their rows are committed under one
    gf.h2d inside the decode."""
    data, parity = shards
    lost = {1, 5, 9, 11}
    have = {i: (data[i] if i < 8 else parity[i - 8])
            for i in range(12) if i not in lost}
    want = _codec("numpy").decode(lost, have, data.shape[1])
    codec = _codec()
    out, spans = _profiled(tmp_path,
                           lambda: codec.decode(lost, have, data.shape[1]))
    assert sorted(out) == sorted(want)
    for i in want:
        assert np.asarray(out[i]).tobytes() == want[i].tobytes()
    names = {s[0] for s in spans}
    assert names == {"ec.decode", "ec.decode_matrix", "gf.h2d",
                     "gf.dispatch", "gf.wait", "gf.d2h"}
    assert names <= set(DEVICE_STAGES)
    _assert_nested(spans, "gf.", {"ec.decode"})
    _assert_nested(spans, "gf.h2d", {"ec.decode"})
    _assert_nested(spans, "ec.decode_matrix", {"ec.decode"})
    # one commit of the survivors, one launch and one fetch of the lost
    # rows, whatever is lost
    count = {n: sum(s[0] == n for s in spans) for n in names}
    assert count["gf.h2d"] == count["gf.dispatch"] == count["gf.wait"] \
        == count["gf.d2h"] == 1
    # a matrix build is a cache miss, one span each
    assert count["ec.decode_matrix"] == \
        codec._jax_codec.rebuild_matrix_misses == 1
    # a second decode of the same pattern finds its matrix cached
    _, again = _profiled(tmp_path / "again",
                         lambda: codec.decode(lost, have, data.shape[1]))
    assert "ec.decode_matrix" not in {s[0] for s in again}
    assert codec._jax_codec.rebuild_matrix_hits == 1


def test_clay_repair_stages_and_bytes(tmp_path):
    """A Clay single-shard repair of three stripes from the helpers'
    repair-plane sub-chunks: one clay.repair around the matrix build,
    the gather, one gf.* round trip and the scatter."""
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    codec = ErasureCodePluginRegistry.instance().factory(
        {"plugin": "clay", "k": "4", "m": "2", "technique": "cauchy_orig"})
    Z, sub, lost = codec.sub_chunk_count, 32, 3
    data = np.random.default_rng(5).integers(0, 256, (3, 4, Z * sub),
                                             dtype=np.uint8)
    shards = np.concatenate(
        [data, np.stack([codec.encode_chunks(s) for s in data])], axis=1)
    planes = codec.repair_planes(lost)
    helpers = {h: np.ascontiguousarray(
        shards[:, h].reshape(3, Z, sub)[:, planes]).reshape(-1)
        for h in range(6) if h != lost}
    out, spans = _profiled(tmp_path,
                           lambda: codec.decode({lost}, helpers, Z * sub))
    assert out[lost].tobytes() == shards[:, lost].tobytes()
    names = {s[0] for s in spans}
    assert names == {"clay.repair", "clay.repair_matrix", "clay.gather",
                     "clay.scatter", "gf.h2d", "gf.dispatch", "gf.wait",
                     "gf.d2h"}
    assert names <= set(DEVICE_STAGES)
    for inner in ("clay.repair_matrix", "clay.gather", "clay.scatter",
                  "gf."):
        _assert_nested(spans, inner, {"clay.repair"})
    count = {n: sum(s[0] == n for s in spans) for n in names}
    assert count["clay.repair"] == count["gf.dispatch"] == 1
    assert (codec.repair_matrix_hits, codec.repair_matrix_misses) == (0, 1)
    assert codec.repair_bytes_in == 5 * 3 * len(planes) * sub
    assert codec.repair_bytes_out == 3 * Z * sub
    _, again = _profiled(tmp_path / "again",
                         lambda: codec.decode({lost}, helpers, Z * sub))
    assert "clay.repair_matrix" not in {s[0] for s in again}
    assert codec.repair_matrix_hits == 1


def test_shec_decode_stages_and_bytes(tmp_path, monkeypatch):
    """A SHEC(6,3,2) single-erasure decode from its least read set: one
    shec.decode around the plan and matrix build, one commit of the
    three rows, one launch, one wait and one fetch; the matrix cache and
    chunk counters move, and a second decode finds its matrix cached."""
    from ceph_tpu.common.kernel_telemetry import TELEMETRY
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    monkeypatch.setattr(TELEMETRY, "enabled", True)

    def counter(name):
        return TELEMETRY.perf.dump().get(name, 0)

    codec = ErasureCodePluginRegistry.instance().factory(
        {"plugin": "shec", "k": "6", "m": "3", "c": "2"})
    data = np.random.default_rng(6).integers(0, 256, (6, 4096),
                                             dtype=np.uint8)
    shards = dict(enumerate(np.concatenate([data,
                                            codec.encode_chunks(data)])))
    lost = 4
    plan = codec.minimum_to_decode({lost}, set(shards) - {lost})
    have = {c: shards[c] for c in plan}
    before = {n: counter(n) for n in ("shec_matrix_hits",
                                      "shec_matrix_misses",
                                      "shec_chunks_read")}
    out, spans = _profiled(tmp_path,
                           lambda: codec.decode({lost}, have, 4096))
    assert out[lost].tobytes() == data[lost].tobytes()
    names = {s[0] for s in spans}
    assert names == {"shec.decode", "shec.decode_matrix", "gf.h2d",
                     "gf.dispatch", "gf.wait", "gf.d2h"}
    assert names <= set(DEVICE_STAGES)
    for inner in ("shec.decode_matrix", "gf."):
        _assert_nested(spans, inner, {"shec.decode"})
    count = {n: sum(s[0] == n for s in spans) for n in names}
    assert count["shec.decode"] == count["gf.h2d"] == \
        count["gf.dispatch"] == count["gf.wait"] == count["gf.d2h"] == 1
    assert counter("shec_matrix_misses") == before["shec_matrix_misses"] + 1
    assert counter("shec_chunks_read") == \
        before["shec_chunks_read"] + len(plan) == \
        before["shec_chunks_read"] + 3
    _, again = _profiled(tmp_path / "again",
                         lambda: codec.decode({lost}, have, 4096))
    assert "shec.decode_matrix" not in {s[0] for s in again}
    assert counter("shec_matrix_hits") == before["shec_matrix_hits"] + 1


def _osdmap():
    from ceph_tpu.crush import CrushWrapper, build_hierarchical_map
    from ceph_tpu.osd import OSDMap

    m = OSDMap(CrushWrapper(build_hierarchical_map(8, 4)))
    m.create_pool(1, pg_num=64, size=3, crush_rule=0, name="rbd")
    m.mark_down(5)
    return m


def _assert_map_pool_matches_scalar(m, up_b, prim_b):
    from ceph_tpu.crush import ITEM_NONE

    pool = m.pools[1]
    for ps in range(pool.pg_num):
        up, upp, _, _ = m.pg_to_up_acting_osds(1, ps)
        assert list(up_b[ps]) == up + [ITEM_NONE] * (pool.size - len(up))
        assert prim_b[ps] == upp


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["one_launch", "chunked"])
def test_map_pool_stages_and_answers(tmp_path, monkeypatch, chunked):
    from ceph_tpu.crush import mapper

    m = _osdmap()
    m.map_pool(1)  # compiles outside the session
    if chunked:
        # 3 lanes a PG: four rule-fn launches of 20, 20, 20 and a
        # ragged 4 PGs, each waiting and fetching on its own
        monkeypatch.setattr(mapper, "_BATCH_CHUNK", 60)
        m.map_pool(1)
    (up_b, prim_b), spans = _profiled(tmp_path, lambda: m.map_pool(1))
    _assert_map_pool_matches_scalar(m, up_b, prim_b)
    names = {s[0] for s in spans}
    assert names == {"osdmap.map_pool", "crush.launch", "crush.wait",
                     "crush.d2h", "osdmap.post_pass"}
    assert names <= set(DEVICE_STAGES)
    _assert_nested(spans, "crush.", {"osdmap.map_pool"})
    _assert_nested(spans, "osdmap.post_pass", {"osdmap.map_pool"})
    if chunked:
        count = {n: sum(s[0] == n for s in spans) for n in names}
        # one of each per chunk, plus map_pool's own
        assert count["crush.wait"] == count["crush.d2h"] == 5


def test_stage_without_a_session(monkeypatch):
    monkeypatch.setattr(TRACER, "enabled", False)
    with stage("ec.encode"):
        with stage("ec_encode_inline", ("abc", "def")):
            pass


def test_stage_label_carries_the_trace_id(tmp_path, monkeypatch):
    """Recorded whenever a profiler session collects, with cephtrace's
    own sampling off."""
    monkeypatch.setattr(TRACER, "enabled", False)

    def traced():
        with stage("ec_encode_inline", ("abc", "def", "ghi")):
            pass
        with stage("ec_encode_inline", ("xyz",)):
            pass

    _, spans = _profiled(tmp_path, traced)
    assert [s[1] for s in spans] == ["ec_encode_inline#trace=abc+2",
                                     "ec_encode_inline#trace=xyz"]


def test_every_stage_is_documented():
    with open(DOCS) as f:
        text = f.read()
    for name in DEVICE_STAGES:
        assert f"| `{PREFIX}{name}` |" in text, name
