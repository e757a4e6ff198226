"""Pieces every driver shares: seeded streams, the reference loader, the
compile meter.  Each is a copy of a sound piece of the program, kept here
so that a change to the program cannot change the yardstick."""
from __future__ import annotations

import importlib
import threading

import numpy as np

#: stream ids for derive_rng; a new driver appends, never renumbers
STREAMS = {
    "data": 0,        # codec batches
    "erasures": 1,    # per-call erasure patterns
    "changes": 2,     # per-call map changes
    "sample": 3,      # which answers the check compares
}


def derive_rng(seed: int, stream: str, index: int = 0) -> np.random.Generator:
    """One independent generator per (run seed, purpose, actor).

    Copied from ceph_tpu/bench/traffic.py::derive_rng: numpy seeds by
    entropy-pooling the whole int sequence, so streams never collide and
    any seed up to and past 2**32 is taken whole."""
    return np.random.default_rng([int(seed), STREAMS[stream], int(index)])


def load_reference(config: dict):
    """The plain reference a configuration names by its `reference` key:
    benchmark/reference/<name>.py."""
    return importlib.import_module("reference." + config["reference"])


def seeded_bytes(rng: np.random.Generator, *shape: int) -> np.ndarray:
    """Seeded uint8 array, made in bulk."""
    return rng.integers(0, 256, size=shape, dtype=np.uint8)


class CompileMeter:
    """Backend compile time and persistent-cache hits and misses, from
    JAX's own monitoring events.  Copied from chip_smoke.py::CompileMeter
    (compiles may run on daemon threads, hence the lock), plus a count of
    backend compiles so that a compile inside the window shows."""

    def __init__(self):
        self._lock = threading.Lock()
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

    def on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += duration
                self.compiles += 1

    def on_event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snap(self) -> dict:
        with self._lock:
            return {"compile_s": self.secs, "compiles": self.compiles,
                    "cache_hits": self.hits, "cache_misses": self.misses}
