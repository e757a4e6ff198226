"""Each cell's check catches the faults its timed path can have, and its
control: the harness runs a whole cell at the mix's rehearsal sizes on
the CPU (Pallas in interpret mode, no look for a chip) with the program
broken underneath, and `correct` has to come out false.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Faults, where the cell can have them: a step that returns its state
unchanged (an earlier answer again), half of the batch left out, one
answer altered where it is produced.  The exchange between chips has no
fault here: every cell runs on one chip.
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

SECONDS = 2.0


def _stale(first: dict, fn):
    def wrapped(*a, **kw):
        out = fn(*a, **kw)
        return first.setdefault("out", out)
    return wrapped


def _codec_faults(monkeypatch, fault: str, method: str):
    from ceph_tpu.ec.plugins.rs import RSCodec

    orig = getattr(RSCodec, method)

    def edit(out):
        if isinstance(out, dict):
            return {s: edit(v) for s, v in out.items()}
        out = np.array(out, dtype=np.uint8)
        if fault == "half":
            out[..., out.shape[-1] // 2:] = 0
        else:
            out.flat[0] ^= 1
        return out

    if fault == "stale":
        fn = _stale({}, orig)
    else:
        def fn(self, *a, **kw):
            return edit(orig(self, *a, **kw))
    monkeypatch.setattr(RSCodec, method, fn)


def _map_pool_faults(monkeypatch, fault: str):
    from ceph_tpu.osd.osdmap import OSDMap

    orig = OSDMap.map_pool
    if fault == "stale":
        fn = _stale({}, orig)
    else:
        def fn(self, pool_id):
            up, primary = orig(self, pool_id)
            up, primary = up.copy(), primary.copy()
            if fault == "half":
                up[len(up) // 2:] = -1
                primary[len(up) // 2:] = -1
            else:
                up[0, 0] = (up[0, 0] + 1) % self.max_osd
            return up, primary
    monkeypatch.setattr(OSDMap, "map_pool", fn)


PLANT = {
    "rs84.encode_1mib": lambda mp, f: _codec_faults(mp, f, "encode_chunks"),
    "rs84.decode4_1mib": lambda mp, f: _codec_faults(mp, f, "decode_chunks"),
    "crush1024.map_pool_osd_down": _map_pool_faults,
}


def _run(cell: str, **kw) -> dict:
    return run.run_cell(cell, 20260001, SECONDS, False, rehearse=True, **kw)


@pytest.mark.parametrize("fault", ["stale", "half", "altered"])
@pytest.mark.parametrize("cell", sorted(PLANT))
def test_fault_reads_incorrect(cell, fault, monkeypatch):
    out = _run(cell, driver_hook=lambda d: PLANT[cell](monkeypatch, fault))
    assert out["correct"] is False, out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("cell", sorted(PLANT))
def test_control_reads_incorrect(cell):
    out = _run(cell, control=True)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", sorted(PLANT))
def test_sound_run_reads_correct(cell):
    out = _run(cell)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
