"""Codec driver: the erasure-code plugin's own entry points, host buffer
in and host chunks out.

The configuration's `profile` makes the codec through the plugin
registry; its `reference` names the plain reference module
(benchmark/reference/<name>.py) that builds the coding matrix from the
same profile, and its `control_technique` the technique whose matrix the
control puts in the program's place.

Mix keys: `op` ("encode": encode_chunks of every batch; "decode": erase
`erasures` of the k+m shards and recover them through decode),
`objects_per_call` x `object_bytes` per call, laid out as shard j = chunk
j of every object back to back; `batches` distinct seeded batches made
at set-up, cycled over; `keep_every` (about one call in this many keeps
its answer for the check, drawn from the seed, and the last always
does); `decode_block` (erasure patterns per block, by how many parity
shards each erases: every block does the same work, in a seeded order).
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import derive_rng, load_reference, seeded_bytes


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, mix = ctx.config, ctx.mix
        self.k, self.m = int(cfg["k"]), int(cfg["m"])
        self.op = mix["op"]
        self.ref = load_reference(cfg)
        self.L = mix["objects_per_call"] * mix["object_bytes"] // self.k
        self.user_bytes = self.k * self.L
        self.attempted = 0
        self.failed = 0
        self.kept: list[tuple] = []
        self.calls = 0
        self.elapsed = 0.0
        self.applies: dict[tuple, int] = {}

    # -- set-up ----------------------------------------------------------
    def setup(self):
        from ceph_tpu.ec.registry import ErasureCodePluginRegistry

        ctx, mix = self.ctx, self.ctx.mix
        self.codec = ErasureCodePluginRegistry.instance().factory(
            dict(ctx.config["profile"]))
        rng = derive_rng(ctx.seed, "data")
        self.batches = [seeded_bytes(rng, self.k, self.L)
                        for _ in range(mix["batches"])]
        self.keep_rng = derive_rng(ctx.seed, "sample")
        if self.op == "encode":
            self.codec.encode_chunks(self.batches[0])
        else:
            # the stored parity every decode call starts from
            self.parity = [np.asarray(self.codec.encode_chunks(b))
                           for b in self.batches]
            self.patterns = self._pattern_stream()
            # warm each decode shape: one pattern per count of parity
            # shards erased
            e = mix["erasures"]
            for p in sorted(int(p) for p in mix["decode_block"]):
                self._decode(0, list(range(self.k - (e - p), self.k + p)))
        if ctx.control:
            self.use_control()

    def _pattern_stream(self):
        rng = derive_rng(self.ctx.seed, "erasures")
        e = self.ctx.mix["erasures"]
        counts = {int(p): c for p, c in self.ctx.mix["decode_block"].items()}
        while True:
            block = [p for p, c in counts.items() for _ in range(c)]
            rng.shuffle(block)
            for p in block:
                par = rng.choice(self.m, p, replace=False) + self.k
                dat = rng.choice(self.k, e - p, replace=False)
                yield sorted(int(s) for s in np.concatenate([dat, par]))

    # -- the timed path ----------------------------------------------------
    def _decode(self, b: int, lost: list[int]) -> dict:
        data, parity = self.batches[b], self.parity[b]
        avail = {i: (data[i] if i < self.k else parity[i - self.k])
                 for i in range(self.k + self.m) if i not in lost}
        return self.codec.decode(set(lost), avail, self.L)

    def _encode(self, b: int):
        return self.codec.encode_chunks(self.batches[b])

    def call(self, i: int):
        b = i % len(self.batches)
        if self.op == "encode":
            self._count(self.m, self.k, self.L)
            return b, None, self._encode(b)
        lost = next(self.patterns)
        self._count(self.k, self.k, self.L)
        p = len([s for s in lost if s >= self.k])
        if p:
            self._count(p, self.k, self.L)
        return b, lost, self._decode(b, lost)

    # -- the control ---------------------------------------------------------
    def use_control(self):
        """Put the reference in the program's place with the matrix of
        the configuration's `control_technique` (cauchy_orig, jerasure's
        unimproved Cauchy matrix, for a cauchy_good profile): parity that
        is no longer bit-exact with the stated technique, and decodes
        that trust it."""
        cfg, ref = self.ctx.config, self.ref
        orig = ref.coding_matrix(dict(cfg["profile"],
                                      technique=cfg["control_technique"]))

        def encode(b):
            return ref.apply(orig, self.batches[b])

        def decode(b, lost):
            data, parity = self.batches[b], self.parity[b]
            have = {i: (data[i] if i < self.k else parity[i - self.k])
                    for i in range(self.k + self.m) if i not in lost}
            return ref.decode(orig, have, lost)

        self._encode, self._decode = encode, decode

    def _count(self, rows, n, length):
        key = (rows, n, length)
        self.applies[key] = self.applies.get(key, 0) + 1

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
        return {"gf_applies": [(r, n, L, c) for (r, n, L), c
                               in sorted(self.applies.items())],
                "calls": self.calls}

    def release(self):
        self.codec = None
        self.parity = None

    def check(self) -> dict:
        """Bytes of the kept answers that differ from the reference (a
        missing or misshapen answer counts all its bytes)."""
        ref_parity: dict[int, np.ndarray] = {}
        coding = self.ref.coding_matrix(self.ctx.config["profile"])

        def parity(b):
            if b not in ref_parity:
                ref_parity[b] = self.ref.apply(coding, self.batches[b])
            return ref_parity[b]

        wrong = 0
        for b, lost, out in self.kept:
            if lost is None:
                wrong += diff(out, parity(b))
                continue
            for s in lost:
                want = (self.batches[b][s] if s < self.k
                        else parity(b)[s - self.k])
                got = out.get(s) if isinstance(out, dict) else None
                wrong += diff(got, want)
        self.ctx.log(f"compared {len(self.kept)} of {self.calls} calls")
        name = "parity_bytes_wrong" if self.op == "encode" \
            else "recovered_bytes_wrong"
        return {name: {"value": int(wrong), "limit": 0}}


def diff(got, want: np.ndarray) -> int:
    if got is None:
        return int(want.size)
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != np.uint8:
        return int(want.size)
    return int(np.count_nonzero(got != want))
