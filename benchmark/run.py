#!/usr/bin/env python
"""Run one benchmark cell once and print its result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`.  It names a
configuration (benchmark/configs/<config>.json) and a traffic mix
(benchmark/mixes/<traffic>.json); the mix names a driver
(benchmark/drivers/<driver>.py) that sets up the system under test, runs
the measured window and checks the answers against the plain reference
(benchmark/reference/).  Each per-layer metric is read by
benchmark/layer_metrics/<metric>.py, or by the file of the part of its
name before the first dot.  Adding a configuration, a mix or a metric is
adding files and entries; this file does not change.

One run: set-up (inputs from --seed, program start, warm-up of every
shape the window uses), then --seconds of measured work, then the check.
With --trace 1 the window runs under the JAX profiler and the line
carries the per-layer metrics; with --trace 0 the end-to-end ones.  The
last line of stdout is one JSON object; the numbers compared, each with
its limit, are the last lines of stderr and the line's last key.

Off the chip the run fails: no line, exit 3.  --rehearse runs the cell at
the mix's toy sizes on the CPU with Pallas in interpret mode and prints a
marked line that is never a result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"# bench: {msg}", file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, traffic: str | None = None) -> dict:
    """The cell `name` with its configuration, mix and metrics;
    `traffic` puts another mix of the same driver in the cell's place."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = dict(load_json(os.path.join(ROOT, configs[cell["config"]]["file"])))
    mix = load_json(os.path.join(BENCH, "mixes",
                                 (traffic or cell["traffic"]) + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": e2e, "per_layer": per_layer}


def metric_reader(name: str):
    """benchmark/layer_metrics/<name>.py, else the file named by the part
    of `name` before its first dot (one reader serving a split metric)."""
    d = os.path.join(BENCH, "layer_metrics")
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(d, stem + ".py")
        if os.path.isfile(path):
            return load_module(path, "bench_metric_" + stem.replace(".", "_"))
    raise FileNotFoundError(f"no reader for per-layer metric {name!r} in {d}")


class Context:
    """What a driver gets: its configuration and mix (the mix's
    `rehearse` sizes laid over it in a rehearsal), the seed, and a
    TraceAnnotation factory for the benchmark's own host spans."""

    def __init__(self, cell: dict, seed: int, rehearse: bool, trace: bool,
                 control: bool = False):
        import jax

        self.cell = cell["cell"]
        self.config = cell["config"]
        mix = dict(cell["mix"])
        overrides = mix.pop("rehearse", {})
        if rehearse:
            mix.update(overrides)
        self.mix = mix
        self.seed = int(seed)
        self.trace = trace
        #: the driver puts its control in the program's place (control.py)
        self.control = control
        self.log = log
        self._annotation = jax.profiler.TraceAnnotation

    def span(self, name: str):
        return self._annotation("bench:" + name)


class LayerContext:
    def __init__(self, trace, counters: dict, device_kind: str, log):
        self.trace = trace
        self.counters = counters
        self.device_kind = device_kind
        self.log = log


def configure_jax(rehearse: bool):
    # the TPU runtime's logs would go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # the kernels the chip runs, in interpret mode
        os.environ["CEPH_TPU_EC_KERNEL"] = "pallas"
        os.environ["CEPH_TPU_CRUSH_ENGINE"] = "limb"
        os.environ["CEPH_TPU_CRUSH_SCORE"] = "pallas"
    import jax

    # a fixed directory in the checkout, so that every run after a cell's
    # first finds its programs; sub-second compiles are kept too
    jax.config.update("jax_compilation_cache_dir", os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size cap: the cache holds a few MiB, and the capped cache's
    # eviction fails every write once one entry lacks its access-time file
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, driver_hook=None,
             traffic: str | None = None, control: bool = False) -> dict:
    """One run of cell `name`; returns the result object.  `control`
    runs the driver's control in the program's place; `driver_hook`
    (tests) is called with the driver after its set-up."""
    cell = load_cell(name, traffic)
    for d in (ROOT, BENCH):
        if d not in sys.path:
            sys.path.insert(0, d)
    jax = configure_jax(rehearse)
    devices = jax.devices()
    chips = int(cell["cell"].get("chips", 1))
    if not rehearse:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX's first device is {devices[0].platform!r} "
                         f"({devices[0].device_kind}); this runs on the chip")
        if len(devices) < chips:
            raise NoChip(f"{len(devices)} chips, the cell asks for {chips}")
    from benchlib import CompileMeter

    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter.on_duration)
    jax.monitoring.register_event_listener(meter.on_event)

    drv_mod = load_module(os.path.join(BENCH, "drivers",
                                       cell["mix"]["driver"] + ".py"),
                          "bench_driver_" + cell["mix"]["driver"])
    ctx = Context(cell, seed, rehearse, trace, control)
    driver = drv_mod.Driver(ctx)
    driver.setup()
    if driver_hook is not None:
        driver_hook(driver)
    setup_s = time.perf_counter() - T_START
    before = meter.snap()
    log(f"set-up {setup_s:.3f} s, {before}")

    trace_dir = os.path.join(CACHE, "trace", name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with ctx.span("window"):
            driver.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    after = meter.snap()
    log(f"window closed, {after}")
    in_window = after["compiles"] - before["compiles"]
    if in_window:
        log(f"{in_window} backend compile(s) inside the window")

    dev = devices[0]
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    e2e = driver.end_to_end()
    counters = driver.counters()
    attempted, failed = driver.attempted, driver.failed
    if hasattr(driver, "collect"):
        driver.collect()
    driver.release()
    gc.collect()

    metrics: dict = {}
    breakdown = None
    if trace:
        from trace_reduce import load as load_trace

        tr = load_trace(trace_dir)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        lctx = LayerContext(tr, counters, dev.device_kind, log)
        for m in cell["per_layer"]:
            value = metric_reader(m["name"]).read(lctx, m)
            if value is None:
                log(f"per-layer metric {m['name']}: nothing to read, left out")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    else:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        for m in cell["end_to_end"]:
            if m["name"] == "setup_s":
                continue
            if m["name"] not in e2e:
                raise KeyError(f"driver reported no {m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    checks = driver.check()
    correct = (attempted > 0 and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["window_compiles"] = in_window
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU, Pallas in interpret mode; "
                         "prints a marked line, never a result")
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), rehearse=args.rehearse)
    except NoChip as e:
        log(f"FAIL: {e}")
        return 3
    except Exception:
        traceback.print_exc()
        log("FAIL: the run did not complete")
        return 1
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"check correct = {out['correct']}", file=sys.stderr, flush=True)
    line = json.dumps(out)
    if args.rehearse:
        print("# rehearsal on the CPU, not a result: " + line, flush=True)
    else:
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
