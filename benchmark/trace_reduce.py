"""Reduce a JAX profiler trace (xplane) to the numbers the per-layer
metrics read: device busy time, kernel time by name, the top device
operations and the idle gaps by what the host was doing.

A device plane is one whose name starts with "/device:TPU:"; its
operations are the events of its "XLA Ops" line.  The host's own spans
are the benchmark's `jax.profiler.TraceAnnotation`s, whose names start
with "bench:"; "bench:window" brackets the traced window.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


class KernelMissing(LookupError):
    """No device operation matched a kernel's name pattern."""


@dataclass
class Trace:
    #: device plane name -> [(op name, start_ns, end_ns)]
    devices: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    #: host spans [(name, start_ns, end_ns)], names starting "bench:"
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    @property
    def window(self) -> tuple[float, float]:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if not w:
            raise LookupError(f"no {WINDOW_SPAN!r} span in the trace")
        return min(s for s, _ in w), max(e for _, e in w)

    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def _ops(self):
        lo, hi = self.window
        for plane, evs in self.devices.items():
            for name, s, e in evs:
                if e > lo and s < hi:
                    yield plane, name, max(s, lo), min(e, hi)

    def busy_s(self) -> float:
        """Seconds of the window in which some operation ran, per device
        (the union of its operations' intervals), averaged over devices."""
        if not self.devices:
            return 0.0
        lo, hi = self.window
        total = 0.0
        for plane in self.devices:
            ivs = [(s, e) for p, _, s, e in self._ops() if p == plane]
            total += union_ns(ivs)
        return total / len(self.devices) / 1e9

    def kernel(self, pattern: str) -> tuple[float, list[str]]:
        """(seconds, distinct op names) of the device operations in the
        window whose name matches `pattern`, summed over devices.
        Raises KernelMissing when none matches."""
        rx = re.compile(pattern)
        secs = 0.0
        names: set[str] = set()
        for _, name, s, e in self._ops():
            if rx.search(name):
                secs += (e - s) / 1e9
                names.add(short_name(name))
        if not names:
            raise KernelMissing(f"no device operation matches {pattern!r}")
        return secs, sorted(names)

    def top_ops(self, n: int = 10) -> list[list]:
        """[[op name, seconds], ...]: the operations that took most device
        time in the window, summed over their calls and devices."""
        agg: dict[str, float] = {}
        for _, name, s, e in self._ops():
            k = short_name(name)
            agg[k] = agg.get(k, 0.0) + (e - s) / 1e9
        return [[k, v] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """[[host span name, seconds], ...]: device idle time in the
        window, each gap charged to the innermost host span that covers
        its midpoint ("(none)" where no span does), longest first."""
        lo, hi = self.window
        inner = [(nm, s, e) for nm, s, e in self.spans if nm != WINDOW_SPAN]
        agg: dict[str, float] = {}
        for plane in self.devices:
            ivs = merge([(s, e) for p, _, s, e in self._ops() if p == plane])
            cur = lo
            for s, e in ivs + [(hi, hi)]:
                if s > cur:
                    mid = (cur + s) / 2
                    cover = [(e2 - s2, nm) for nm, s2, e2 in inner
                             if s2 <= mid <= e2]
                    who = min(cover)[1] if cover else "(none)"
                    agg[who] = agg.get(who, 0.0) + (s - cur) / 1e9
                cur = max(cur, e)
        per_dev = max(1, len(self.devices))
        return [[k, v / per_dev] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def short_name(op: str) -> str:
    """'%fusion.12 = u8[...] fusion(...)' -> 'fusion.12'."""
    head = op.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def merge(ivs: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(ivs: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merge(ivs))


def from_profile(pd) -> Trace:
    """A Trace from a jax.profiler.ProfileData."""
    t = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            t.devices[plane.name] = evs
        else:
            for line in plane.lines:
                t.spans.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events
                               if e.name.startswith(SPAN_PREFIX))
    return t


def load(log_dir: str) -> Trace:
    """The Trace of the newest xplane file under a profiler log dir."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {log_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))
