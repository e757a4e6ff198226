"""SHEC recovery driver: one lost chunk of a SHEC pool's objects rebuilt
through the plugin's `decode` from exactly the chunks its
`minimum_to_decode` names, as an OSD's recovery reads them.

The configuration's `profile` makes the codec through the plugin
registry; its `reference` (benchmark/reference/shec_ref.py) builds the
coding matrix from the same profile, finds the least read set by brute
force and rebuilds, and its `control_technique` names the shingle layout
the control rebuilds with.

Mix keys: `objects_per_call` x `object_bytes`, each object padded with
zeros to whole stripes of k x the configuration's `stripe_unit` and
striped, so that shard j is chunk j of every object back to back;
`batches` distinct seeded batches made at set-up, cycled over;
`keep_every` (about one call in this many keeps its answer for the
check, drawn from the seed, and the last always does).

Set-up: every batch encoded through the plugin's `encode_chunks` (the
first object's parity checked against the reference first, so a program
that cannot build the profile's code fails here, in seconds); one decode
a lost shard, to warm every matrix and shape.  Each call: the next lost
shard, from seeded blocks of k+m in which every shard is lost once, its
read set from `minimum_to_decode`, then `decode({lost}, those chunks)`.
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
        self.k, self.m = int(cfg["k"]), int(cfg["m"])
        self.su = int(cfg["stripe_unit"])
        self.objects = int(mix["objects_per_call"])
        self.object_bytes = int(mix["object_bytes"])
        stripe = self.k * self.su
        self.stripes = -(-self.object_bytes // stripe)
        self.L = self.objects * self.stripes * self.su
        self.user_bytes = self.objects * self.object_bytes
        self.coding = self.ref.coding_matrix(self.profile)
        n = self.k + self.m
        #: lost shard -> the reference's least read set
        self.least = {s: self.ref.minimum_read({s}, self.k, self.m,
                                               self.coding)
                      for s in range(n)}
        self.attempted = 0
        self.failed = 0
        self.kept: list[tuple] = []
        self.calls = 0
        self.elapsed = 0.0
        self.extra_reads = 0
        self.lost_counts = [0] * n

    # -- set-up ----------------------------------------------------------
    def setup(self):
        from ceph_tpu.ec.registry import ErasureCodePluginRegistry

        ctx, mix = self.ctx, self.ctx.mix
        self.codec = ErasureCodePluginRegistry.instance().factory(
            dict(self.profile))
        rng = derive_rng(ctx.seed, "data")
        n = self.k + self.m
        #: batch -> [k + m, L] shards
        self.shards = []
        for b in range(mix["batches"]):
            data = self._striped(seeded_bytes(rng, self.objects,
                                              self.object_bytes))
            parity = np.asarray(self.codec.encode_chunks(data))
            if b == 0:
                self._check_first_parity(data, parity)
            self.shards.append(np.concatenate([data, parity]))
        self.others = {s: frozenset(range(n)) - {s} for s in range(n)}
        self.keep_rng = derive_rng(ctx.seed, "sample")
        self.lost_stream = self._lost_stream()
        for lost in range(n):
            _, out = self._rebuild(0, lost)
            got = np.asarray(out[lost])
            if got.size != self.L:
                raise RuntimeError(f"decode rebuilt {got.size} B of shard "
                                   f"{lost}, not {self.L}")
        if ctx.control:
            self.use_control()

    def _striped(self, objects: np.ndarray) -> np.ndarray:
        """[objects, object_bytes] -> [k, L]: each object zero-padded to
        whole stripes, stripe unit j of every stripe in chunk j."""
        padded = np.zeros((self.objects, self.stripes * self.k * self.su),
                          np.uint8)
        padded[:, :self.object_bytes] = objects
        return np.ascontiguousarray(padded.reshape(
            self.objects, self.stripes, self.k, self.su).transpose(
            2, 0, 1, 3)).reshape(self.k, self.L)

    def _check_first_parity(self, data, parity):
        """The program's parity of the first object against the
        reference's."""
        w = self.stripes * self.su
        want = self.ref.apply(self.coding, data[:, :w])
        if not np.array_equal(np.asarray(parity)[:, :w], want):
            raise RuntimeError(
                "the plugin's parity is not the profile's code: "
                f"{self.profile} (a program that cannot build it cannot "
                "run this cell)")

    def _lost_stream(self):
        rng = derive_rng(self.ctx.seed, "erasures")
        while True:
            for lost in rng.permutation(self.k + self.m):
                yield int(lost)

    # -- the timed path ----------------------------------------------------
    def _rebuild(self, b: int, lost: int) -> tuple[int, dict]:
        read = self.codec.minimum_to_decode({lost}, self.others[lost])
        shards = self.shards[b]
        have = {c: shards[c] for c in read}
        return len(have), self.codec.decode({lost}, have, self.L)

    def call(self, i: int):
        b = i % len(self.shards)
        lost = next(self.lost_stream)
        n_read, out = self._rebuild(b, lost)
        self.extra_reads += max(0, n_read - len(self.least[lost]))
        self.lost_counts[lost] += 1
        return b, lost, out

    # -- the control ---------------------------------------------------------
    def use_control(self):
        """Put the reference in the program's place with the shingle
        layout of the configuration's `control_technique` (single, one
        group of m parities): a rebuild that no longer follows the
        stated layout, from its own least read sets."""
        other = self.ref.coding_matrix(
            dict(self.profile, technique=self.ctx.config["control_technique"]))
        least = {s: self.ref.minimum_read({s}, self.k, self.m, other)
                 for s in range(self.k + self.m)}

        def rebuild(b, lost):
            have = {c: self.shards[b][c] for c in least[lost]}
            return len(have), self.ref.rebuild({lost}, have, other)

        self._rebuild = rebuild

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
        return {"shec_decodes": [(len(self.least[s]), self.L, c)
                                 for s, c in enumerate(self.lost_counts)
                                 if c],
                "calls": self.calls}

    def release(self):
        self.codec = None

    def check(self) -> dict:
        """Bytes of the kept rebuilt chunks that differ from the
        reference's rebuild of the same chunk from its least read set of
        the reference's shards (a missing or misshapen answer counts all
        its bytes); and the chunks every call handed to decode beyond
        the reference's least read set."""
        want: dict[tuple, np.ndarray] = {}
        parity: dict[tuple, np.ndarray] = {}

        def shard(b, c):
            if c < self.k:
                return self.shards[b][c]
            if (b, c) not in parity:
                p = c - self.k
                parity[b, c] = self.ref.apply(self.coding[p:p + 1],
                                              self.shards[b][:self.k])[0]
            return parity[b, c]

        def rebuilt(b, lost):
            if (b, lost) not in want:
                have = {c: shard(b, c) for c in self.least[lost]}
                want[b, lost] = self.ref.rebuild({lost}, have,
                                                 self.coding)[lost]
            return want[b, lost]

        wrong = 0
        for b, lost, out in self.kept:
            got = out.get(lost) if isinstance(out, dict) else None
            wrong += diff(got, rebuilt(b, lost))
        self.ctx.log(f"compared {len(self.kept)} of {self.calls} calls")
        return {"rebuilt_bytes_wrong": {"value": int(wrong), "limit": 0},
                "extra_chunks_read": {"value": int(self.extra_reads),
                                      "limit": 0}}


def diff(got, want: np.ndarray) -> int:
    if got is None:
        return int(want.size)
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != np.uint8:
        return int(want.size)
    return int(np.count_nonzero(got != want))
