"""The program's own host spans in this run's profile: the
`cephtrace:<stage>` spans that ceph_tpu/common/tracer.py `stage` writes
into the profiler session, on the clock of the device operations.

trace_reduce keeps only the benchmark's `bench:` spans.  This reads the
same xplane file for the program's spans, each with its host thread (the
xplane line), so that a span's self time subtracts only the spans nested
in it on its own thread.  A program without such spans yields an empty
list, and the readers that use it read nothing.
"""
from __future__ import annotations

import glob
import os

from trace_reduce import WINDOW_SPAN, Trace, union_ns

PREFIX = "cephtrace:"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: where run.py has the profiler write each cell's traced window
PROFILES = os.path.join(ROOT, ".bench_cache", "trace", "*", "plugins",
                        "profile", "*", "*.xplane.pb")

#: (path, mtime, size) -> (bench:window bounds, spans): one parse a run
_parsed: dict = {}


def from_profile(pd, window) -> list[tuple[str, str, float, float]] | None:
    """[(stage, thread, start_ns, end_ns)] of the cephtrace spans of a
    jax.profiler.ProfileData that overlap `window`, clipped to it; None
    unless the profile's bench:window spans exactly `window`."""
    return _in_window(_read(pd), window)


def load(ctx, pattern: str | None = None):
    """from_profile of the newest profile under `pattern` (PROFILES),
    parsed once a run, in the window of `ctx.trace`; None (and a log
    line) where that profile is not the one `ctx.trace` was read from."""
    pattern = pattern or PROFILES
    paths = glob.glob(pattern)
    if not paths:
        ctx.log(f"program spans: no profile matches {pattern}")
        return None
    path = max(paths, key=os.path.getmtime)
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _parsed:
        from jax.profiler import ProfileData

        _parsed.clear()
        _parsed[key] = _read(ProfileData.from_file(path))
    spans = _in_window(_parsed[key], ctx.trace.window)
    if spans is None:
        ctx.log(f"program spans: the bench:window of {path} is "
                f"{_parsed[key][0]}, this run's trace has {ctx.trace.window}")
    return spans


def _read(pd):
    """(bench:window bounds or None, [(stage, thread, start, end)])."""
    bounds: list[tuple[float, float]] = []
    spans = []
    for plane in pd.planes:
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}:{line.name}"
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    bounds.append((e.start_ns, e.end_ns))
                elif e.name.startswith(PREFIX):
                    stage = e.name[len(PREFIX):].split("#", 1)[0]
                    spans.append((stage, thread, e.start_ns, e.end_ns))
    window = (min(s for s, _ in bounds), max(e for _, e in bounds)) \
        if bounds else None
    return window, spans


def _in_window(parsed, window):
    own, spans = parsed
    if own != tuple(window):
        return None
    lo, hi = window
    return [(n, t, max(s, lo), min(e, hi)) for n, t, s, e in spans
            if e > lo and s < hi]


def attribution(trace: Trace, spans) -> Trace:
    """`trace` with the program's spans beside the benchmark's, so that
    Trace.idle_gaps charges each gap to the innermost of either."""
    return Trace(devices=trace.devices,
                 spans=trace.spans + [(PREFIX + n, s, e)
                                      for n, _, s, e in spans])


def self_ns(spans) -> list[float]:
    """Each span's self time: its duration less the union of the spans
    nested in it on the same thread (those it directly encloses cover
    the rest)."""
    out = [0.0] * len(spans)
    by_thread: dict[str, list[int]] = {}
    for i, (_, thread, _, _) in enumerate(spans):
        by_thread.setdefault(thread, []).append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i][2], -spans[i][3]))
        kids: dict[int, list[tuple[float, float]]] = {i: [] for i in idx}
        open_: list[int] = []
        for i in idx:
            s, e = spans[i][2], spans[i][3]
            while open_ and not e <= spans[open_[-1]][3]:
                open_.pop()
            if open_:
                kids[open_[-1]].append((s, e))
            open_.append(i)
        for i in idx:
            out[i] = spans[i][3] - spans[i][2] - union_ns(kids[i])
    return out


def stage_of(name: str, family: str) -> bool:
    """`name` is the stage `family` or one of its `family.*` stages."""
    return name == family or name.startswith(family + ".")
