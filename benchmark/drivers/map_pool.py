"""Placement driver: OSDMap.map_pool over every PG of the configuration's
pool, one map change before each call.

Mix keys: `change` ("osd_down": the OSD marked down by the previous call
comes back up and another, drawn from the seed, goes down; "reweight": an
OSD drawn from the seed gets a reweight drawn from [0, 1), cumulatively);
`keep_every` (about one call in this many keeps its answer for the check,
drawn from the seed, and the last always does); `pg_num` (optional, a
rehearsal's smaller pool).
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import derive_rng
from reference import crush_ref


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, mix = ctx.config, ctx.mix
        self.pg_num = int(mix.get("pg_num", cfg["pg_num"]))
        self.n_osd = cfg["hosts"] * cfg["osds_per_host"]
        self.change = mix["change"]
        self.attempted = 0
        self.failed = 0
        self.kept: list[tuple] = []
        self.calls = 0
        self.elapsed = 0.0
        self.down: int | None = None
        self._answer = self._program
        self.tree = crush_ref.HostTree(cfg["hosts"], cfg["osds_per_host"])

    def setup(self):
        from ceph_tpu.crush import CrushWrapper, build_hierarchical_map
        from ceph_tpu.osd.osdmap import OSDMap

        cfg = self.ctx.config
        self.osdmap = OSDMap(CrushWrapper(build_hierarchical_map(
            cfg["hosts"], cfg["osds_per_host"],
            device_weight=cfg["osd_crush_weight"])))
        self.osdmap.create_pool(cfg["pool_id"], self.pg_num, cfg["size"],
                                cfg["rule_id"])
        self.reweight = np.full(self.n_osd, 0x10000, np.int64)
        self.changes = derive_rng(self.ctx.seed, "changes")
        self.keep_rng = derive_rng(self.ctx.seed, "sample")
        self.osdmap.map_pool(cfg["pool_id"])  # compiles the one shape
        if self.ctx.control:
            self.use_control()

    # -- the timed path ----------------------------------------------------
    def _apply_change(self):
        o = int(self.changes.integers(0, self.n_osd))
        if self.change == "osd_down":
            if self.down is not None:
                self.osdmap.mark_up(self.down)
            self.osdmap.mark_down(o)
            self.down = o
        elif self.change == "reweight":
            w = int(self.changes.random() * 0x10000)
            self.osdmap.osd_weight[o] = w
            self.reweight[o] = w
        else:
            raise ValueError(f"unknown change {self.change!r}")
        return self.down, self.reweight.copy() if self.change == "reweight" \
            else None

    def _program(self, state):
        return self.osdmap.map_pool(self.ctx.config["pool_id"])

    def window(self, seconds: float):
        keep_p = 1.0 / self.ctx.mix["keep_every"]
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with self.ctx.span("call"):
                state = self._apply_change()
                up, primary = self._answer(state)
            self.attempted += 1
            self.calls += 1
            now = time.perf_counter()
            rec = (state, np.asarray(up), np.asarray(primary))
            keep = self.keep_rng.random() < keep_p
            if keep:
                self.kept.append(rec)
            if now >= deadline:
                break
        self.elapsed = now - t0
        if not keep:
            self.kept.append(rec)

    # -- the control: the reference one map change behind ------------------
    def use_control(self):
        """Put the reference in the program's place, answering from the
        map as it was before the call's change (a stale epoch)."""
        self._stale = (None, np.full(self.n_osd, 0x10000, np.int64))

        def control(state):
            down, rw = self._stale
            self._stale = (state[0], state[1] if state[1] is not None
                           else rw)
            return self._reference((down, rw))

        self._answer = control

    # -- results -----------------------------------------------------------
    def end_to_end(self) -> dict:
        return {"crush_maps_s": self.calls * self.pg_num / self.elapsed}

    def counters(self) -> dict:
        return {"pgs_mapped": self.calls * self.pg_num, "calls": self.calls}

    def release(self):
        self.osdmap = None

    def _reference(self, state, raw_cache: dict | None = None):
        cfg = self.ctx.config
        down, rw = state
        rw = rw if rw is not None else np.full(self.n_osd, 0x10000, np.int64)
        key = rw.tobytes()
        if raw_cache is not None and key in raw_cache:
            raw = raw_cache[key]
        else:
            raw = crush_ref.pool_raw(self.tree, cfg["pool_id"],
                                     self.pg_num, cfg["size"], rw)
            if raw_cache is not None:
                raw_cache[key] = raw
        return crush_ref.up_set(raw, down)

    def _osd_or_none(self, a: np.ndarray) -> np.ndarray:
        """OSD ids as they are, every other value as -1: "no OSD" is
        compared by meaning, since the program encodes it as
        -0x7FFFFFFE where Ceph's CRUSH_ITEM_NONE is 0x7FFFFFFF."""
        a = np.asarray(a, np.int64)
        return np.where((a >= 0) & (a < self.n_osd), a, -1)

    def check(self) -> dict:
        """PGs of the kept answers whose up set or up primary differs
        from the reference."""
        cache: dict = {}
        wrong = 0
        for state, up, primary in self.kept:
            want_up, want_primary = self._reference(state, cache)
            if up.shape != want_up.shape or primary.shape != want_primary.shape:
                wrong += self.pg_num
                continue
            up, primary, want_up, want_primary = (
                self._osd_or_none(a) for a in (up, primary, want_up,
                                                want_primary))
            wrong += int(((up != want_up).any(axis=1)
                          | (primary != want_primary)).sum())
        self.ctx.log(f"compared {len(self.kept)} of {self.calls} calls")
        return {"pgs_wrong": {"value": wrong, "limit": 0}}
