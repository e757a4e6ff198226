"""cephdev (ISSUE 10): kernel telemetry registry + backend health
sentinel + the mon health-check surface.

Fast (~10 s class, per the tier-1 budget rule): unit coverage drives
the registry/sentinel directly with canned probes; the one cluster test
arms the conditions via the sentinel's forced state + a recorded
fallback latch and asserts the `status`/`health detail` output both
RAISES and CLEARS.  Everything process-global is restored in teardown —
tests run alphabetically and this file executes early.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ceph_tpu.common.kernel_telemetry import (
    SENTINEL,
    TELEMETRY,
    BackendSentinel,
    KernelTelemetry,
    SentinelPolicy,
    backend_health,
    default_probe,
    dump_kernel_telemetry,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_global_state():
    """The registry/sentinel are process-wide: leave them as found."""
    was_enabled = TELEMETRY.enabled
    yield
    TELEMETRY.enable(was_enabled)
    TELEMETRY.clear_fallback()
    SENTINEL.reset_state()
    os.environ.pop("CEPH_TPU_SENTINEL_STATE", None)


# -- registry ----------------------------------------------------------

class TestKernelTelemetry:
    def test_record_dump_and_perf_mirror(self):
        tm = KernelTelemetry()
        tm.record("k1", "xla", 0.002, bytes_in=4096, bytes_out=2048,
                  compiled=True)
        tm.record("k1", "pallas", 0.001, bytes_in=4096, bytes_out=2048,
                  synced=True)
        d = tm.dump()["k1"]
        assert d["calls"] == 2
        assert d["bytes_in"] == 8192 and d["bytes_out"] == 4096
        assert d["backends"] == {"xla": 1, "pallas": 1}
        assert d["compiles"] == 1
        assert d["last_backend"] == "pallas"
        # synced call yields achieved GiB/s; async leaves it untouched
        assert d["last_gibps"] == pytest.approx(4096 / 0.001 / 2**30)
        # the PerfCounters mirror: one histogram sample per bucket kind
        pd = tm.perf.dump()
        assert pd["k1_calls"] == 2
        assert pd["k1_compile"]["count"] == 1
        assert pd["k1_execute"]["count"] == 1
        assert pd["k1_gibps"] == pytest.approx(d["last_gibps"])
        # schema carries HELP text for the prometheus exporter
        sch = tm.perf.schema()
        assert sch["k1_execute"]["type"] == "histogram"
        assert "k1" in sch["k1_execute"]["description"]

    def test_disabled_is_inert(self):
        tm = KernelTelemetry()
        tm.enable(False)
        tm.record("k1", "xla", 0.001, bytes_in=100)
        assert tm.dump() == {}

    def test_first_call_discriminates_compile(self):
        tm = KernelTelemetry()
        key = ("k", (2, 2), (2, 64), "xla")
        assert tm.first_call(key) is True
        assert tm.first_call(key) is False

    def test_fallback_latch_and_clear_events(self):
        tm = KernelTelemetry()
        tm.record_fallback("gf_apply", "mosaic boom", frm="pallas",
                           to="xla")
        latched = tm.fallback_latched()
        assert latched["gf_apply"]["reason"] == "mosaic boom"
        assert latched["gf_apply"]["ts"] > 0
        assert tm.clear_fallback() is True
        assert tm.fallback_latched() == {}
        assert tm.clear_fallback() is False  # idempotent
        kinds = [e["kind"] for e in tm.events()]
        assert kinds == ["fallback_latched", "fallback_cleared"]

    def test_dispatch_seam_records_gf_apply(self):
        TELEMETRY.enable(True)
        from ceph_tpu.ops.bitplane import apply_matrix_jax

        before = TELEMETRY.dump().get("gf_apply", {}).get("calls", 0)
        mat = np.array([[1, 2], [3, 4]], np.uint8)
        chunks = np.arange(8, dtype=np.uint8).reshape(2, 4)
        apply_matrix_jax(mat, chunks)
        d = TELEMETRY.dump()["gf_apply"]
        assert d["calls"] == before + 1
        assert d["last_backend"] in ("xla", "pallas")

    def test_stream_encode_records_synced_gibps(self):
        TELEMETRY.enable(True)
        from ceph_tpu.ops.pipeline import stream_encode

        mat = np.array([[1, 2], [3, 4]], np.uint8)
        batches = [np.random.default_rng(i).integers(
            0, 256, (2, 256), dtype=np.uint8) for i in range(3)]
        outs = stream_encode(mat, iter(batches))
        assert len(outs) == 3
        d = TELEMETRY.dump()["stream_encode"]
        assert d["bytes_in"] >= 3 * 512
        assert d["last_gibps"] is not None and d["last_gibps"] > 0

    def test_crush_batch_records(self):
        TELEMETRY.enable(True)
        from ceph_tpu.crush import (
            CompiledCrushMap,
            build_hierarchical_map,
            crush_do_rule_batch,
        )

        cmap = build_hierarchical_map(4, 2)
        cm = CompiledCrushMap(cmap)
        weights = np.full(8, 0x10000, dtype=np.uint32)
        xs = np.arange(64, dtype=np.int64)
        np.asarray(crush_do_rule_batch(cm, 0, xs, 3, weights))
        d = TELEMETRY.dump()["crush_do_rule_batch"]
        assert d["calls"] >= 1
        assert d["compiles"] >= 1  # fresh rule-fn cache = a compile


# -- sentinel ----------------------------------------------------------

class TestBackendSentinel:
    def test_probe_failure_latches_and_recovery_clears(self):
        calls = {"n": 0}

        def probe():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("tunnel down")
            return "cpu"

        s = BackendSentinel(SentinelPolicy(interval=0.1, timeout=0.5,
                                           probe=probe))
        st = s.probe_once()
        assert st["state"] == "degraded" and s.degraded()
        assert "tunnel down" in st["reason"]
        assert st["since"] is not None
        st = s.probe_once()
        assert st["state"] == "ok" and not s.degraded()
        assert st["platform"] == "cpu"
        assert st["transitions"] == 2

    def test_hung_probe_latches_fast_and_does_not_stack(self):
        release = threading.Event()

        def probe():
            release.wait(5.0)
            return "cpu"

        # boot_timeout pinned too: the cold-boot grace (first probe)
        # would otherwise give this hung probe 15 s
        s = BackendSentinel(SentinelPolicy(interval=0.1, timeout=0.1,
                                           probe=probe, boot_timeout=0.1))
        t0 = time.monotonic()
        st = s.probe_once()
        assert time.monotonic() - t0 < 1.0  # fast timeout, no wedge
        assert st["state"] == "degraded"
        assert "timed out" in st["reason"]
        # second cycle sees the worker still hung: no new worker stacked
        st = s.probe_once()
        assert st["state"] == "degraded"
        assert "still hung" in st["reason"]
        release.set()

    def test_env_probe_override(self, monkeypatch):
        monkeypatch.setenv("CEPH_TPU_SENTINEL_STATE", "degraded:forced!")
        with pytest.raises(RuntimeError, match="forced!"):
            default_probe()
        monkeypatch.setenv("CEPH_TPU_SENTINEL_STATE", "ok")
        assert default_probe() == "forced-ok"

    def test_failpoint_probe_arm(self):
        from ceph_tpu.common.failpoint import registry

        registry().set("tpu.backend.probe", "error")
        try:
            s = BackendSentinel(SentinelPolicy(interval=0.1, timeout=0.5))
            assert s.probe_once()["state"] == "degraded"
        finally:
            registry().set("tpu.backend.probe", "off")

    def test_boot_grace_then_fast_timeout(self):
        """The first (cold) probe gets the boot grace; once the runtime
        has answered, the fast timeout governs."""
        slow = {"on": False}

        def probe():
            if slow["on"]:
                time.sleep(0.5)
            return "cpu"

        s = BackendSentinel(SentinelPolicy(interval=0.1, timeout=0.05,
                                           probe=probe, boot_timeout=2.0))
        assert s.probe_once()["state"] == "ok"  # cold probe rides grace
        slow["on"] = True
        st = s.probe_once()  # answered once: 0.05s budget now applies
        assert st["state"] == "degraded" and "0.05" in st["reason"]

    def test_force_applies_immediately_and_pins(self):
        s = BackendSentinel(SentinelPolicy(interval=0.1, timeout=0.5,
                                           probe=lambda: "cpu"))
        s.force("degraded", "test pin")
        assert s.degraded()
        s.probe_once()  # probe would say ok; the pin wins
        assert s.degraded()
        s.force(None)
        assert s.probe_once()["state"] == "ok"

    def test_degraded_blocks_auto_pallas(self):
        from ceph_tpu.ops import bitplane

        SENTINEL.force("degraded", "test")
        try:
            assert bitplane._want_pallas() is False
            assert bitplane.current_backend() == "xla"
        finally:
            SENTINEL.reset_state()

    def test_refcounted_lifecycle(self):
        s = BackendSentinel(SentinelPolicy(interval=0.05, timeout=0.2,
                                           probe=lambda: "cpu"))
        s.acquire()
        s.acquire()
        assert s.running()
        s.release()
        assert s.running()  # one holder left
        s.release()
        assert not s.running()

    def test_backend_health_blob_shape(self):
        bh = backend_health()
        assert set(bh) == {"sentinel", "fallback"}
        assert "state" in bh["sentinel"]
        d = dump_kernel_telemetry()
        assert {"enabled", "kernels", "fallback", "sentinel",
                "events"} <= set(d)


# -- prometheus rendering ----------------------------------------------

def test_render_metrics_health_and_kernel_series():
    from ceph_tpu.mgr.prometheus_module import render_metrics

    health = {"health": {"status": "HEALTH_WARN", "checks": {
        "TPU_BACKEND_DEGRADED": {"severity": "HEALTH_WARN",
                                 "message": "1 daemon degraded"},
    }}}
    tm = KernelTelemetry()
    tm.record("gf_apply", "xla", 0.001, bytes_in=4096, bytes_out=2048,
              synced=True)
    reports = {"osd.0": {"kernel": tm.perf.dump()}}
    schema = {"kernel": tm.perf.schema()}
    text = render_metrics(None, reports, schema=schema, health=health)
    assert "ceph_health_status 1" in text
    assert ('ceph_health_detail{name="TPU_BACKEND_DEGRADED",'
            'severity="HEALTH_WARN"} 1') in text
    # per-kernel series with HELP from the schema path
    assert "# HELP ceph_kernel_gf_apply_calls gf_apply kernel" in text
    assert 'ceph_kernel_gf_apply_calls{ceph_daemon="osd.0"} 1' in text
    # the execute histogram renders as a real prometheus histogram
    assert "# TYPE ceph_kernel_gf_apply_execute histogram" in text
    assert 'ceph_kernel_gf_apply_execute_count{ceph_daemon="osd.0"} 1' \
        in text
    # HEALTH_OK renders 0
    ok = render_metrics(None, {}, health={"health": {
        "status": "HEALTH_OK", "checks": {}}})
    assert "ceph_health_status 0" in ok


# -- the mon health-check surface (cluster) ----------------------------

def test_cluster_health_checks_raise_and_clear():
    """Arm each condition (forced sentinel state + recorded fallback
    latch) and assert `status`/`health detail` output — then clear both
    and assert the checks retract.  The whole OSD -> mgr digest -> mon
    `_health` pipeline, one fast cluster."""
    from ceph_tpu.qa.vstart import LocalCluster

    overrides = {
        "backend_sentinel_interval": 0.1,
        "backend_sentinel_timeout": 0.5,
        "mgr_report_interval": 0.2,
        "mgr_digest_interval": 0.2,
    }
    os.environ["CEPH_TPU_SENTINEL_STATE"] = "degraded:test wedge"
    TELEMETRY.record_fallback("gf_apply", "test mosaic failure")
    try:
        with LocalCluster(n_mons=1, n_osds=2, with_mgr=True,
                          conf_overrides=overrides) as c:
            def checks():
                rv, res = c.mon_command({"prefix": "health detail"})
                assert rv == 0, (rv, res)
                return (res.get("health") or {}).get("checks") or {}

            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                got = checks()
                if {"TPU_BACKEND_DEGRADED",
                        "KERNEL_FALLBACK_LATCHED"} <= set(got):
                    break
                time.sleep(0.2)
            got = checks()
            chk = got.get("TPU_BACKEND_DEGRADED")
            assert chk, f"TPU_BACKEND_DEGRADED missing: {sorted(got)}"
            assert chk["severity"] == "HEALTH_WARN"
            assert chk["daemons"], chk
            assert any("test wedge" in d for d in chk["detail"]), chk
            fb = got.get("KERNEL_FALLBACK_LATCHED")
            assert fb, f"KERNEL_FALLBACK_LATCHED missing: {sorted(got)}"
            assert any("test mosaic failure" in d for d in fb["detail"])
            # overall status degrades
            rv, res = c.mon_command({"prefix": "status"})
            assert res["health"]["status"] == "HEALTH_WARN"

            # -- recovery: probe says ok, latch cleared ---------------
            os.environ["CEPH_TPU_SENTINEL_STATE"] = "ok"
            TELEMETRY.clear_fallback()
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                got = checks()
                if not ({"TPU_BACKEND_DEGRADED",
                         "KERNEL_FALLBACK_LATCHED"} & set(got)):
                    break
                time.sleep(0.2)
            got = checks()
            assert "TPU_BACKEND_DEGRADED" not in got, sorted(got)
            assert "KERNEL_FALLBACK_LATCHED" not in got, sorted(got)
    finally:
        os.environ.pop("CEPH_TPU_SENTINEL_STATE", None)
        TELEMETRY.clear_fallback()


# -- entry points off the chip ---------------------------------------------

def _no_json_line(out: str) -> bool:
    for line in out.splitlines():
        try:
            json.loads(line)
            return False
        except ValueError:
            continue
    return True


def test_bench_fails_loudly_off_the_chip():
    """On a CPU host bench.py exits non-zero, names the missing chip and
    prints no JSON line (no CPU number under a device metric)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr, p.stderr
    assert _no_json_line(p.stdout), p.stdout


def test_chip_smoke_fails_loudly_off_the_chip(tmp_path):
    """chip_smoke.py exits non-zero and prints no result on a CPU host,
    and in a directory that holds nothing of the repo but itself."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr, p.stderr
    assert p.stdout == "", p.stdout
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    p = subprocess.run([sys.executable, str(lone)], capture_output=True,
                       text=True, timeout=120, env=env, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout == "", p.stdout
