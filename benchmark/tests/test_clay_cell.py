"""The Clay repair cell's check catches the faults its timed path can
have, and its control, at the mix's rehearsal sizes on the CPU (Pallas
in interpret mode): a repair that returns an earlier answer again, half
of the rebuilt bytes left out, one byte altered.  And the roofline
reader's bytes by hand.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_clay_cell.py
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

CELL = "clay84.repair_4mib"


def _plant(monkeypatch, fault: str):
    from ceph_tpu.ec.plugins.clay import ClayCodec

    orig = ClayCodec.decode
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

    monkeypatch.setattr(ClayCodec, "decode", decode)


def _run(**kw) -> dict:
    return run.run_cell(CELL, 20260001, 2.0, False, rehearse=True, **kw)


@pytest.mark.parametrize("fault", ["stale", "half", "altered"])
def test_fault_reads_incorrect(fault, monkeypatch):
    out = _run(driver_hook=lambda d: _plant(monkeypatch, fault))
    assert out["correct"] is False, out["checks"]


def test_control_reads_incorrect():
    out = _run(control=True)
    assert out["correct"] is False, out["checks"]


def test_sound_run_reads_correct():
    out = _run()
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_repair_bytes_by_hand():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "clay_repair_roofline",
        os.path.join(BENCH, "layer_metrics", "clay_repair_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # 11 helpers x 16 planes in, 64 planes out, 8 KiB sub-chunks, 64
    # objects: 120 MiB a call
    assert mod.repair_bytes(11, 64, 4, 8192, 64) == (176 + 64) * 8192 * 64
