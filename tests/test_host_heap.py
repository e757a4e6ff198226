"""The host allocator policy behind the codec's device-to-host fetch
(common/host_heap.py) and the fetch's counters (ec/plugins/rs.py
`_to_host`, `d2h_fetches` / `d2h_minor_faults`)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from ceph_tpu.common import host_heap
from ceph_tpu.common.kernel_telemetry import TELEMETRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _glibc_linux() -> bool:
    try:
        return sys.platform.startswith("linux") and bool(
            os.confstr("CS_GNU_LIBC_VERSION"))
    except (ValueError, OSError):
        return False


def test_policy_is_idempotent_and_reports_whether_it_applied(monkeypatch):
    monkeypatch.setattr(host_heap, "_applied", None)
    first = host_heap.retain_freed_large_blocks()
    assert first is _glibc_linux()

    def fail():
        raise AssertionError("the policy was applied twice")

    monkeypatch.setattr(host_heap, "_apply", fail)
    assert host_heap.retain_freed_large_blocks() is first


def test_policy_is_a_no_op_off_glibc(monkeypatch):
    monkeypatch.setattr(host_heap, "_applied", None)
    monkeypatch.setattr(host_heap, "_glibc", lambda: None)
    assert host_heap.retain_freed_large_blocks() is False
    assert host_heap.retain_freed_large_blocks() is False


def _bitplane_codec():
    from ceph_tpu.ops.bitplane import BitplaneCodec

    return BitplaneCodec(np.ones((4, 8), dtype=np.uint8))


def _clay_codec():
    from ceph_tpu.ec.plugins.clay import ClayCodec

    return ClayCodec({"k": "4", "m": "2", "d": "5"})


@pytest.mark.parametrize("build", [_bitplane_codec, _clay_codec],
                         ids=["rs", "clay"])
def test_building_the_jax_codec_engages_the_policy(monkeypatch, build):
    monkeypatch.setattr(host_heap, "_applied", None)
    build()
    assert host_heap._applied is _glibc_linux()


_FAULTS_OF_A_SECOND_FILL = textwrap.dedent("""
    import json, resource, threading
    import numpy as np
    from ceph_tpu.common.host_heap import retain_freed_large_blocks

    def faults():
        return resource.getrusage(resource.RUSAGE_SELF).ru_minflt

    def second_fill_faults(nbytes):
        for _ in range(2):
            before = faults()
            buf = np.empty(nbytes, dtype=np.uint8)
            buf.fill(1)
            del buf
        return faults() - before

    def in_a_thread(fn):
        # a worker thread allocates from an arena of its own, whose first
        # heap a 40 MiB block fills, so 32 MiB more takes a second heap
        out = []

        def work():
            held = np.ones(40 << 20, dtype=np.uint8)
            out.append(fn(32 << 20))
            del held

        t = threading.Thread(target=work)
        t.start()
        t.join(30)
        return out[0]

    def fills():
        return [second_fill_faults(32 << 20), second_fill_faults(64 << 20),
                in_a_thread(second_fill_faults)]

    control = fills()
    applied = retain_freed_large_blocks()
    print(json.dumps({"control": control, "applied": applied,
                      "after": fills()}))
""")


@pytest.mark.skipif(not _glibc_linux(), reason="glibc on Linux only")
def test_second_large_fill_after_the_policy_takes_no_new_pages():
    """A second 32 MiB and 64 MiB allocate-and-fill, in the main heap and
    in a worker thread's second heap, faults in no new pages."""
    out = subprocess.run(
        [sys.executable, "-c", _FAULTS_OF_A_SECOND_FILL], cwd=REPO,
        capture_output=True, text=True, timeout=60, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    if min(got["control"]) <= 8:
        pytest.skip(f"this kernel counts no faults on a fresh mapping: {got}")
    assert got["applied"] is True
    assert max(got["after"]) <= 8, got


def _counter(name: str) -> float:
    return TELEMETRY.perf.dump().get(name, 0)


def test_to_host_returns_the_result_and_counts_the_fetch(monkeypatch):
    import jax.numpy as jnp

    from ceph_tpu.ec.plugins.rs import _to_host

    monkeypatch.setattr(TELEMETRY, "enabled", True)
    want = np.random.default_rng(7).integers(0, 256, (4, 4096),
                                             dtype=np.uint8)
    fetches, faults = _counter("d2h_fetches"), _counter("d2h_minor_faults")
    got = _to_host(jnp.asarray(want))
    assert isinstance(got, np.ndarray)
    assert got.dtype == np.uint8 and got.shape == (4, 4096)
    np.testing.assert_array_equal(got, want)
    assert _counter("d2h_fetches") == fetches + 1
    assert _counter("d2h_minor_faults") >= faults
    assert "d2h_minor_faults" in TELEMETRY.perf.dump()

    monkeypatch.setattr(TELEMETRY, "enabled", False)
    np.testing.assert_array_equal(_to_host(jnp.asarray(want)), want)
    assert _counter("d2h_fetches") == fetches + 1
