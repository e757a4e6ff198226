"""Clay repair driver: one lost shard of a CLAY pool's objects rebuilt
through the plugin's `decode` from the helpers' repair-plane sub-chunks
alone, as the OSD's recovery reads them.

The configuration's `profile` makes the codec through the plugin
registry; its `reference` (benchmark/reference/clay_ref.py) encodes and
repairs from the same profile, and its `control_technique` names the
inner code the control repairs with.

Mix keys: `objects_per_call` x `object_bytes`, each object one stripe
(its chunk is object_bytes / k); `batches` distinct seeded batches made
at set-up, cycled over; `keep_every` (about one call in this many keeps
its answer for the check, drawn from the seed, and the last always
does).

Set-up: every object encoded through the plugin's `encode_chunks` (the
first object's parity checked against the reference, so a program that
cannot express the profile fails here); for each batch and lost shard,
the d helpers' buffers, each object's Z/q repair-plane sub-chunks object
after object; one repair a lost shard, to warm every matrix.  Each call:
the next lost shard, from seeded blocks of k+m in which every shard is
lost once, then `decode({lost}, helpers, chunk)`.
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import derive_rng, load_reference, seeded_bytes


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, mix = ctx.config, ctx.mix
        self.profile = dict(cfg["profile"])
        self.ref = load_reference(cfg)
        k, m, d, q, _, Z = self.ref.geometry(self.profile)
        self.k, self.m, self.d, self.q, self.Z = k, m, d, q, Z
        self.objects = int(mix["objects_per_call"])
        self.chunk = int(mix["object_bytes"]) // k
        if self.chunk % Z:
            raise ValueError(f"chunk {self.chunk} B is not {Z} sub-chunks")
        self.sub_len = self.chunk // Z
        self.user_bytes = self.objects * k * self.chunk
        self.attempted = 0
        self.failed = 0
        self.kept: list[tuple] = []
        self.calls = 0
        self.elapsed = 0.0

    # -- set-up ----------------------------------------------------------
    def setup(self):
        from ceph_tpu.ec.registry import ErasureCodePluginRegistry

        ctx, mix = self.ctx, self.ctx.mix
        self.codec = ErasureCodePluginRegistry.instance().factory(
            dict(self.profile))
        rng = derive_rng(ctx.seed, "data")
        n = self.k + self.m
        #: batch -> [objects, k, chunk] data
        self.batches = []
        #: batch -> lost shard -> {helper: its repair-plane bytes}
        self.helpers = []
        for b in range(mix["batches"]):
            data = seeded_bytes(rng, self.objects, self.k, self.chunk)
            shards = np.empty((self.objects, n, self.chunk), np.uint8)
            shards[:, :self.k] = data
            for o in range(self.objects):
                shards[o, self.k:] = self.codec.encode_chunks(data[o])
                if b == 0 and o == 0:
                    self._check_first_parity(data[0], shards[0, self.k:])
            self.batches.append(data)
            self.helpers.append([self._helper_buffers(shards, lost)
                                 for lost in range(n)])
        self.keep_rng = derive_rng(ctx.seed, "sample")
        self.lost_stream = self._lost_stream()
        for lost in range(n):
            got = np.asarray(self._repair(0, lost)[lost])
            if got.size != self.objects * self.chunk:
                raise RuntimeError(
                    f"decode rebuilt {got.size} B of shard {lost}, not "
                    f"{self.objects} x {self.chunk}")
        if ctx.control:
            self.use_control()

    def _check_first_parity(self, data, parity):
        """The program's parity of one object against the reference's."""
        Z, s = self.Z, self.sub_len
        want = self.ref.encode(self.profile, data.reshape(self.k, Z, s))
        if not np.array_equal(np.asarray(parity).reshape(self.m, Z, s),
                              want):
            raise RuntimeError(
                "the plugin's parity is not the profile's code: "
                f"{self.profile} (a program that ignores the profile's "
                "inner code cannot run this cell)")

    def _helper_buffers(self, shards, lost) -> dict[int, np.ndarray]:
        planes = self.ref.repair_planes(self.profile, lost)
        return {h: np.ascontiguousarray(
            shards[:, h].reshape(self.objects, self.Z, self.sub_len)
            [:, planes]).reshape(-1)
            for h in range(self.k + self.m) if h != lost}

    def _lost_stream(self):
        rng = derive_rng(self.ctx.seed, "erasures")
        while True:
            for lost in rng.permutation(self.k + self.m):
                yield int(lost)

    # -- the timed path ----------------------------------------------------
    def _repair(self, b: int, lost: int) -> dict:
        return self.codec.decode({lost}, self.helpers[b][lost], self.chunk)

    def call(self, i: int):
        b = i % len(self.batches)
        lost = next(self.lost_stream)
        return b, lost, self._repair(b, lost)

    # -- the control ---------------------------------------------------------
    def use_control(self):
        """Put the reference in the program's place with the inner code
        of the configuration's `control_technique` (cauchy_orig,
        jerasure's unimproved Cauchy matrix, for a cauchy_good profile):
        a repair that no longer follows the stated inner code."""
        orig = dict(self.profile,
                    technique=self.ctx.config["control_technique"])
        n_b, S, s = self.Z // self.q, self.objects, self.sub_len

        def repair(b, lost):
            plane_major = {
                h: buf.reshape(S, n_b, s).transpose(1, 0, 2).reshape(n_b, -1)
                for h, buf in self.helpers[b][lost].items()}
            out = self.ref.repair(orig, lost, plane_major)
            return {lost: out.reshape(self.Z, S, s).transpose(
                1, 0, 2).reshape(-1)}

        self._repair = repair

    def window(self, seconds: float):
        keep_p = 1.0 / self.ctx.mix["keep_every"]
        t0 = time.perf_counter()
        deadline = t0 + seconds
        last = None
        i = 0
        while True:
            with self.ctx.span("call"):
                b, lost, out = self.call(i)
            self.attempted += 1
            i += 1
            now = time.perf_counter()
            keep = self.keep_rng.random() < keep_p
            if keep:
                self.kept.append((b, lost, out))
            if now >= deadline:
                if not keep:
                    last = (b, lost, out)
                break
        self.elapsed = now - t0
        self.calls = i
        if last is not None:
            self.kept.append(last)

    # -- results -----------------------------------------------------------
    def end_to_end(self) -> dict:
        return {"codec_gib_s":
                self.calls * self.user_bytes / self.elapsed / 2**30}

    def counters(self) -> dict:
        return {"clay_repairs": [(self.d, self.Z, self.q, self.sub_len,
                                  self.objects, self.calls)],
                "calls": self.calls}

    def release(self):
        self.codec = None
        self.helpers = None

    def check(self) -> dict:
        """Bytes of the kept rebuilt shards that differ from the data
        itself (a data shard) or from the reference's parity (a parity
        shard); a missing or misshapen answer counts all its bytes."""
        ref_parity: dict[int, np.ndarray] = {}
        S, Z, s = self.objects, self.Z, self.sub_len

        def parity(b):
            if b not in ref_parity:
                plane_major = self.batches[b].reshape(
                    S, self.k, Z, s).transpose(1, 2, 0, 3).reshape(
                    self.k, Z, S * s)
                ref_parity[b] = self.ref.encode(
                    self.profile, plane_major).reshape(
                    self.m, Z, S, s).transpose(2, 0, 1, 3).reshape(
                    S, self.m, Z * s)
            return ref_parity[b]

        wrong = 0
        for b, lost, out in self.kept:
            want = (self.batches[b][:, lost] if lost < self.k
                    else parity(b)[:, lost - self.k]).reshape(-1)
            wrong += diff(out.get(lost) if isinstance(out, dict) else None,
                          want)
        self.ctx.log(f"compared {len(self.kept)} of {self.calls} calls")
        return {"repaired_bytes_wrong": {"value": int(wrong), "limit": 0}}


def diff(got, want: np.ndarray) -> int:
    if got is None:
        return int(want.size)
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != np.uint8:
        return int(want.size)
    return int(np.count_nonzero(got != want))
