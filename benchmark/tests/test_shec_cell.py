"""The SHEC recovery cell's checks catch the faults its timed path can
have, and its control, at the mix's rehearsal sizes on the CPU (Pallas
in interpret mode): a decode that returns an earlier answer again, half
of the rebuilt bytes left out, one byte altered, a read set of one chunk
more than the least.  And the roofline reader's bytes by hand.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_shec_cell.py
"""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402

CELL = "shec632.decode1_8mib"


def _plant(monkeypatch, fault: str):
    from ceph_tpu.ec.plugins.shec import ShecCodec

    if fault == "read4":
        orig_min = ShecCodec.minimum_to_decode

        def minimum_to_decode(self, want, available):
            read = dict(orig_min(self, want, available))
            spare = sorted(set(available) - set(read))
            read[spare[0]] = [(0, -1)]
            return read

        monkeypatch.setattr(ShecCodec, "minimum_to_decode",
                            minimum_to_decode)
        return
    orig = ShecCodec.decode
    first: dict = {}

    def decode(self, want, chunks, chunk_size):
        out = orig(self, want, chunks, chunk_size)
        if fault == "stale":
            return first.setdefault("out", out)
        out = {s: np.array(v, dtype=np.uint8) for s, v in out.items()}
        for v in out.values():
            if fault == "half":
                v[v.size // 2:] = 0
            else:
                v[0] ^= 1
        return out

    monkeypatch.setattr(ShecCodec, "decode", decode)


def _run(**kw) -> dict:
    return run.run_cell(CELL, 20260001, 2.0, False, rehearse=True, **kw)


@pytest.mark.parametrize("fault,check", [
    ("stale", "rebuilt_bytes_wrong"), ("half", "rebuilt_bytes_wrong"),
    ("altered", "rebuilt_bytes_wrong"), ("read4", "extra_chunks_read")])
def test_fault_reads_incorrect(fault, check, monkeypatch):
    out = _run(driver_hook=lambda d: _plant(monkeypatch, fault))
    assert out["correct"] is False, out["checks"]
    assert out["checks"][check]["value"] > 0, out["checks"]


def test_control_reads_incorrect():
    out = _run(control=True)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["rebuilt_bytes_wrong"]["value"] > 0


def test_sound_run_reads_correct():
    out = _run()
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_rebuild_bytes_by_hand():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "shec_decode_roofline",
        os.path.join(BENCH, "layer_metrics", "shec_decode_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # 12 objects of 4 MiB, each 171 stripe units of 4 KiB a chunk: three
    # rows read and one written, or six read for the global parity
    L = 12 * 171 * 4096
    assert L == 8_404_992
    assert mod.rebuild_bytes(3, L) == 4 * 8_404_992
    assert mod.rebuild_bytes(6, L) == 7 * 8_404_992
