"""crush_do_rule_batch — the vectorized TPU CRUSH mapper (north-star #2).

Reference: src/crush/mapper.c :: crush_do_rule / crush_choose_firstn /
crush_choose_indep / bucket_straw2_choose, vectorized over the placement
input x exactly as SURVEY.md §3.3 prescribes: all batch consumers (balancer,
crushtool --test, osdmaptool --test-map-pgs) are embarrassingly parallel over
x, and the data-dependent retry loops become masked fixed-trip loops bounded
by choose_total_tries (default 50).

Design:
- The CrushMap is compiled once into dense arrays (items/weights/sizes/types
  padded to the max bucket size) — the analog of CrushWrapper holding the
  crush_map ready for crush_do_rule (reference: src/crush/CrushWrapper.h).
- A rule compiles into a static step plan (TAKE/CHOOSE/EMIT sequence with
  static replica counts — static shapes for XLA); every per-x decision —
  straw2 draws, descent, collisions, is_out rejections, retries — is traced
  jnp over explicit [B] lane arrays (ceph_tpu/crush/batched.py).
- Multi-choose chains (TAKE → CHOOSE rack → CHOOSE host → EMIT) flatten the
  parent axis into the lane axis: a step with W working items per x runs
  one batched choose over N*W lanes, mirroring mapper.c's `for (i = 0;
  i < wsize; i++)` loop over the working vector.
- The straw2 score path is pluggable: full-table ln gather on CPU, the
  fused Pallas hash+ln kernel on TPU (TPUs have no vector gather — see
  ceph_tpu/crush/ln_compute.py).
- int64-exact: draws are div64_s64-style truncating divisions on int64
  (x64 scoped to the CRUSH traces; a global flip breaks Mosaic compiles).

Scope: modern tunables (stable=1, vary_r=1, local retries 0).  The jax
lanes implement straw2 — the algorithm every real deployment uses for
data; maps carrying LEGACY bucket algorithms (uniform/list/tree/straw,
crush.h CRUSH_BUCKET_*) are detected at compile time and the batch API
routes them to the compiled C oracle (tests/test_crush_legacy_buckets.py
proves 3-way bit-exactness).  The scalar Python, the C++ oracle, and
this mapper must agree bit-for-bit on every input — enforced by
tests/test_crush.py over random maps and large x sweeps.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..common.kernel_telemetry import TELEMETRY
from ..common.tracer import stage
from .batched import (
    I64Engine,
    LimbEngine,
    choose_firstn_b,
    choose_indep_b,
    ln_planes_jnp,
    ln_planes_pallas,
    ln_scores_jnp,
    ln_scores_pallas,
)
from .ln_table import CRUSH_LN_TABLE
from .types import ITEM_NONE, CrushMap, RuleOp

# straw2 is 64-bit fixed-point integer math (SURVEY.md §7 hard parts).  x64
# is enabled ONLY around the CRUSH traces (enable_x64 context below) — a
# global jax_enable_x64 flip leaks i64 into unrelated traces and breaks
# Mosaic compilation of the Pallas GF kernel on real TPUs.


def enable_x64():
    """Thread-scoped x64 context for the CRUSH traces."""
    from ..common.jaxutil import x64_ctx

    return x64_ctx(True)


# Max LANES (x times working-set width) per device launch.  Empirically
# (v5e, 1024-OSD hierarchical map): one launch at 1M lanes crashes the TPU
# worker process outright, while <=512k complete; 256k leaves 2x margin and
# still amortizes dispatch to noise.
_BATCH_CHUNK = 1 << 18


def validate_choose_args(
    cmap: CrushMap, name: str
) -> dict[int, list[list[int]]]:
    """Resolve and sanity-check a named choose_args weight-set: the name
    must exist, every bucket id must be a real (negative) bucket, every
    weight_set must be non-empty with rows matching the bucket size.
    Shared by the scalar and batch entry points so malformed maps (e.g.
    hand-edited text) fail identically everywhere."""
    if name not in cmap.choose_args:
        raise KeyError(
            f"unknown choose_args {name!r}; known: {sorted(cmap.choose_args)}"
        )
    ca = cmap.choose_args[name]
    for bid, ws in ca.items():
        if bid >= 0 or bid not in cmap.buckets:
            raise ValueError(f"choose_args {name!r}: no such bucket {bid}")
        if not ws:
            raise ValueError(
                f"choose_args {name!r}: empty weight_set for bucket {bid}"
            )
        size = len(cmap.buckets[bid].items)
        for row in ws:
            if len(row) != size:
                raise ValueError(
                    f"choose_args {name!r}: weight_set row of {len(row)} "
                    f"for bucket {bid} of size {size}"
                )
    return ca


class CompiledCrushMap:
    """Dense-array form of a CrushMap for device execution."""

    def __init__(self, cmap: CrushMap):
        self.cmap = cmap
        ids = sorted(cmap.buckets)
        n_idx = max((-1 - bid for bid in ids), default=-1) + 1
        max_size = max((b.size for b in cmap.buckets.values()), default=1)
        items = np.full((max(n_idx, 1), max_size), ITEM_NONE, dtype=np.int32)
        weights = np.zeros((max(n_idx, 1), max_size), dtype=np.int64)
        sizes = np.zeros(max(n_idx, 1), dtype=np.int32)
        types = np.zeros(max(n_idx, 1), dtype=np.int32)
        algs = np.full(max(n_idx, 1), 5, dtype=np.int32)  # straw2
        straws = np.zeros((max(n_idx, 1), max_size), dtype=np.int64)
        max_nodes = 1
        for b in cmap.buckets.values():
            if getattr(b, "node_weights", None):
                max_nodes = max(max_nodes, len(b.node_weights))
        nodes = np.zeros((max(n_idx, 1), max_nodes), dtype=np.int64)
        counts = np.zeros(max(n_idx, 1), dtype=np.int32)
        for bid, b in cmap.buckets.items():
            i = -1 - bid
            items[i, : b.size] = b.items
            weights[i, : b.size] = b.weights
            sizes[i] = b.size
            types[i] = b.type
            algs[i] = getattr(b, "alg", 5)
            if getattr(b, "straws", None):
                straws[i, : b.size] = b.straws
            if getattr(b, "node_weights", None):
                nodes[i, : len(b.node_weights)] = b.node_weights
                counts[i] = len(b.node_weights)
        self.algs = algs
        self.straws = straws
        self.node_weights = nodes
        self.max_nodes = max_nodes
        #: true per-bucket tree node counts (len(node_weights); 0 = not
        #: a tree bucket) — passed to the oracle verbatim so an ingested
        #: bucket's structural count is authoritative (r4 verdict #5)
        self.node_counts = counts
        #: True iff every bucket is straw2 — the jax/Pallas batch path
        #: covers exactly this; legacy maps route to the C oracle
        self.straw2_only = bool((algs[: max(n_idx, 1)] == 5).all()) if n_idx else True
        with enable_x64():
            self.items = jnp.asarray(items)
            self.weights = jnp.asarray(weights)
            self.sizes = jnp.asarray(sizes)
            self.types = jnp.asarray(types)
            self.ln_table = jnp.asarray(CRUSH_LN_TABLE)
        # int32 plane tables for the limb engine (no x64 anywhere)
        self.ln_hi_table = jnp.asarray(
            (CRUSH_LN_TABLE >> 24).astype(np.int32))
        self.ln_lo_table = jnp.asarray(
            (CRUSH_LN_TABLE & 0xFFFFFF).astype(np.int32))
        self._np_items = items
        self._np_weights = weights
        self._np_sizes = sizes
        self._np_types = types
        self.n_idx = n_idx
        self.max_size = max_size
        self._limb_tables = None
        self._choose_args_cache: dict[str, jnp.ndarray] = {}
        self._choose_args_limb_cache: dict = {}
        self._rule_fn_cache: dict = {}

    @property
    def limb_tables(self):
        """Lazy fat-table build for the TPU limb engine (crush/engine.py)
        — magic divisors + 8-bit gather planes, host-side once per map."""
        if self._limb_tables is None:
            from .engine import LimbTables

            self._limb_tables = LimbTables(
                self._np_items, self._np_weights,
                self._np_sizes, self._np_types,
            )
        return self._limb_tables

    def choose_args_limb(self, name: str):
        """LimbTables over [P * n_idx] rows for a named choose_args
        weight-set (limb-engine twin of choose_args_arrays)."""
        cached = self._choose_args_limb_cache.get(name)
        if cached is not None:
            return cached
        from .engine import LimbTables

        validate_choose_args(self.cmap, name)
        dense = np.asarray(self.choose_args_arrays(name))  # [P, n_idx, S]
        P = dense.shape[0]
        tiled = lambda a: np.tile(a, (P,) + (1,) * (a.ndim - 1)).reshape(
            (P * a.shape[0],) + a.shape[1:]
        )
        tabs = LimbTables(
            tiled(self._np_items),
            dense.reshape(P * self.n_idx, -1),
            tiled(self._np_sizes),
            tiled(self._np_types),
        )
        tabs.positions = P
        self._choose_args_limb_cache[name] = tabs
        return tabs

    def choose_args_arrays(self, name: str) -> jnp.ndarray:
        """Dense [positions, n_idx, max_size] weight array for a named
        choose_args weight-set (reference: crush_choose_arg_map).  Buckets
        without an entry keep their own weights; buckets with fewer
        weight_set rows than the max are clamped to their last row — the
        get_choose_arg_weights position clamp, applied at build time."""
        cached = self._choose_args_cache.get(name)
        if cached is not None:
            return cached
        ca = validate_choose_args(self.cmap, name)
        P = max((len(ws) for ws in ca.values()), default=1)
        base = np.asarray(self.weights)
        dense = np.broadcast_to(base, (P,) + base.shape).copy()
        for bid, ws in ca.items():
            i = -1 - bid
            size = len(self.cmap.buckets[bid].items)
            for p in range(P):
                row = ws[min(p, len(ws) - 1)]
                dense[p, i, :size] = row
        with enable_x64():
            arr = jnp.asarray(dense)
        self._choose_args_cache[name] = arr
        return arr


def compile_plan(cm: CompiledCrushMap, rule_id: int, numrep: int) -> list[dict]:
    """Static step plan for an arbitrary TAKE/(SET_*)/CHOOSE*/EMIT rule
    (the trace-time analog of crush_do_rule's step switch)."""
    rule = cm.cmap.rules[rule_id]
    t = cm.cmap.tunables
    plan: list[dict] = []
    tries = t.choose_total_tries
    leaf_tries = 0
    for step in rule.steps:
        if step.op == RuleOp.TAKE:
            plan.append(dict(op="take", take=step.arg1))
        elif step.op == RuleOp.SET_CHOOSE_TRIES:
            tries = step.arg1
        elif step.op == RuleOp.SET_CHOOSELEAF_TRIES:
            leaf_tries = step.arg1
        elif step.op in (
            RuleOp.CHOOSE_FIRSTN,
            RuleOp.CHOOSE_INDEP,
            RuleOp.CHOOSELEAF_FIRSTN,
            RuleOp.CHOOSELEAF_INDEP,
        ):
            want = step.arg1 if step.arg1 > 0 else numrep + step.arg1
            firstn = step.op in (RuleOp.CHOOSE_FIRSTN, RuleOp.CHOOSELEAF_FIRSTN)
            plan.append(
                dict(
                    op="choose",
                    want=want,
                    type=step.arg2,
                    firstn=firstn,
                    recurse=step.op
                    in (RuleOp.CHOOSELEAF_FIRSTN, RuleOp.CHOOSELEAF_INDEP),
                    tries=tries,
                    leaf_tries=leaf_tries,
                )
            )
        elif step.op == RuleOp.EMIT:
            plan.append(dict(op="emit"))
        else:
            raise ValueError(f"unsupported rule op {step.op}")
    if not any(p["op"] == "choose" for p in plan):
        raise ValueError("rule has no CHOOSE step")
    return plan


def compile_rule(cm: CompiledCrushMap, rule_id: int, numrep: int) -> dict:
    """Single-choose plan (the C++ oracle bridge's fast path); raises on
    anything but the canonical TAKE-CHOOSE-EMIT shape — multi-choose
    chains and EMIT-less rules go through the step interpreter."""
    steps = compile_plan(cm, rule_id, numrep)
    ops = [p["op"] for p in steps]
    if ops != ["take", "choose", "emit"]:
        raise NotImplementedError(
            "the C++ oracle fast path speaks TAKE-CHOOSE-EMIT only"
        )
    return dict(steps[0], **steps[1])


def _firstn_compact(work: jnp.ndarray) -> jnp.ndarray:
    """Dense-pack non-NONE entries left, preserving order (crush_do_rule
    concatenates each parent's successes contiguously into the working
    vector)."""
    is_none = work == ITEM_NONE
    order = jnp.argsort(is_none, axis=1, stable=True)
    return jnp.take_along_axis(work, order, axis=1)


def _build_rule_fn(cm: CompiledCrushMap, rule_id: int, numrep: int,
                   choose_args: str | None, engine_mode: str, score_fn):
    plan = compile_plan(cm, rule_id, numrep)
    if choose_args is None:
        cweights = None
    elif engine_mode == "limb":
        cweights = cm.choose_args_limb(choose_args)
    else:
        cweights = cm.choose_args_arrays(choose_args)
    engine_cls = LimbEngine if engine_mode == "limb" else I64Engine
    if engine_mode == "limb":
        cm.limb_tables  # build the fat tables OUTSIDE the trace

    def fn(xs, weightvec):
        N = xs.shape[0]
        eng = engine_cls(cm, score_fn, weightvec, cweights)
        work = None          # [N, W] current working vector
        emitted = []         # list of [N, w] blocks
        for p in plan:
            if p["op"] == "take":
                work = jnp.full((N, 1), p["take"], jnp.int32)
            elif p["op"] == "choose":
                if work is None:
                    raise ValueError("CHOOSE before TAKE")
                W = work.shape[1]
                want = p["want"]
                parents = work.reshape(N * W)
                x_b = jnp.repeat(xs, W) if W > 1 else xs
                parent_ok = (parents < 0) & (parents != ITEM_NONE)
                fn_b = choose_firstn_b if p["firstn"] else choose_indep_b
                tries = p["tries"]
                recurse_tries = (
                    (p["leaf_tries"] or tries)
                    if p["firstn"]
                    else (p["leaf_tries"] or 1)
                )
                res = fn_b(
                    eng, x_b, parents, want, p["type"],
                    tries, p["recurse"], recurse_tries, parent_ok,
                )
                out, out2 = res[0], res[1]
                chosen = out2 if p["recurse"] else out
                if p["firstn"]:
                    cnt = res[2]
                    chosen = jnp.where(
                        jnp.arange(want)[None, :] < cnt[:, None],
                        chosen,
                        ITEM_NONE,
                    )
                chosen = chosen.reshape(N, W * want)
                if p["firstn"] and W > 1:
                    chosen = _firstn_compact(chosen)
                work = chosen
            else:  # emit
                if work is not None:
                    emitted.append(work)
                work = None
        # un-emitted working items are DROPPED, like crush_do_rule (the
        # scalar mapper agrees; a rule without EMIT maps to nothing)
        if not emitted:
            return jnp.full((N, numrep), ITEM_NONE, jnp.int32)
        result = emitted[0] if len(emitted) == 1 else jnp.concatenate(
            emitted, axis=1
        )
        # contract: [N, numrep] — truncate extra width, pad scarcity
        if result.shape[1] > numrep:
            result = result[:, :numrep]
        elif result.shape[1] < numrep:
            result = jnp.concatenate(
                [
                    result,
                    jnp.full((N, numrep - result.shape[1]), ITEM_NONE, jnp.int32),
                ],
                axis=1,
            )
        return result

    # max lanes any step fans out to, for memory-aware chunking
    width = 1
    max_width = 1
    for p in plan:
        if p["op"] == "take":
            width = 1
        elif p["op"] == "choose":
            width *= p["want"]
            max_width = max(max_width, width)
    return jax.jit(fn), max_width


def default_engine_config(policy=None) -> tuple[str, object, bool]:
    """(engine_mode, score_fn, uses_pallas) for the current backend/env.

    Engine (CEPH_TPU_CRUSH_ENGINE = auto|limb|i64): the LIMB engine
    (crush/engine.py — one-hot fat-table gathers + magic-divisor limb
    draws, no int64/x64) on TPU backends; the I64 gather engine (native
    64-bit divides, fast row gathers) on CPU.

    Score path (CEPH_TPU_CRUSH_SCORE = auto|pallas|gather): the fused
    Pallas hash+ln kernel on TPU (no hardware vector gather — the
    2^16-entry table gather serializes there), the XLA table gather
    elsewhere."""
    import os

    emode = os.environ.get("CEPH_TPU_CRUSH_ENGINE", "auto")
    if emode not in ("auto", "limb", "i64"):
        raise ValueError(
            f"CEPH_TPU_CRUSH_ENGINE={emode!r}: want auto|limb|i64"
        )
    smode = os.environ.get("CEPH_TPU_CRUSH_SCORE", "auto")
    if smode not in ("auto", "pallas", "gather"):
        # a typo'd override silently auto-detecting would defeat its
        # purpose (forcing Pallas on unrecognized TPU aliases)
        raise ValueError(
            f"CEPH_TPU_CRUSH_SCORE={smode!r}: want auto|pallas|gather"
        )
    # backend resolves through the policy seam (cephtopo): a
    # cpu-fallback topology keeps the i64 engine + gather scorer even
    # when an accelerator is visible; callers may inject their own
    # policy (crush_do_rule_batch threads one through)
    from ..common.device_policy import get_device_policy

    pol = policy if policy is not None else get_device_policy()
    on_tpu = pol.backend() == "tpu"
    if emode == "auto":
        emode = "limb" if on_tpu else "i64"
    use_pallas = smode == "pallas" or (smode == "auto" and on_tpu)
    if emode == "limb":
        score = ln_planes_pallas if use_pallas else ln_planes_jnp
    else:
        score = ln_scores_pallas if use_pallas else ln_scores_jnp
    return emode, score, use_pallas


def crush_do_rule_batch(
    cm: CompiledCrushMap,
    rule_id: int,
    xs,
    numrep: int,
    weightvec,
    choose_args: str | None = None,
    policy=None,
) -> jnp.ndarray:
    """Batched crush_do_rule: xs [N] -> [N, numrep] OSD ids.

    The new sibling entry point of CrushWrapper::do_rule that the north star
    adds (SURVEY.md §1 seam #2); consumed by the balancer simulation, the
    crushtool-analog --test, and the osdmaptool-analog --test-map-pgs.
    firstn results are dense with ITEM_NONE tail padding; indep results keep
    positional ITEM_NONE holes (EC shard semantics).  Arbitrary
    TAKE/CHOOSE/EMIT chains are interpreted (multi-choose rules flatten the
    working vector into the lane axis).

    Maps containing LEGACY bucket algorithms (uniform/list/tree/straw)
    route to the compiled C oracle: the jax/Pallas lanes implement
    straw2 — the algorithm every real deployment uses for data — and the
    legacy types exist for map-ingest parity, where C-speed batch
    evaluation is ample (uniform buckets are additionally STATEFUL per
    (x, rule) via their permutation cache, which is hostile to the
    fixed-trip vectorization).

    `policy` (cephtopo) injects a DevicePolicy for the engine/scorer
    pick; None consults the process-wide policy the daemon configured."""
    tm = TELEMETRY
    if not getattr(cm, "straw2_only", True):
        from .oracle_bridge import do_rule_steps_oracle

        t0 = time.perf_counter() if tm.enabled else 0.0
        out = do_rule_steps_oracle(
            cm.cmap, rule_id, np.asarray(xs), numrep,
            np.asarray(weightvec), choose_args, cm=cm,
        )
        if tm.enabled:
            tm.record("crush_do_rule_batch", "oracle",
                      time.perf_counter() - t0,
                      bytes_in=int(np.asarray(xs).nbytes),
                      bytes_out=int(out.nbytes), synced=True)
        return jnp.asarray(out)
    engine_mode, score_fn, uses_pallas = default_engine_config(policy)
    key = (rule_id, numrep, choose_args, engine_mode, uses_pallas)

    def build_and_cache():
        emode, score, _ = default_engine_config(policy)
        built = _build_rule_fn(
            cm, rule_id, numrep, choose_args, emode, score
        ) + (emode,)
        cm._rule_fn_cache[key] = built
        return built

    cached = cm._rule_fn_cache.get(key)
    compiled = cached is None
    if cached is None:
        cached = _build_rule_fn(
            cm, rule_id, numrep, choose_args, engine_mode, score_fn
        ) + (engine_mode,)
        cm._rule_fn_cache[key] = cached
    t0 = time.perf_counter() if tm.enabled else 0.0
    out = _launch_rule_fn(cm, cached, xs, numrep, weightvec)
    if tm.enabled:
        # dispatch-side wall time (the result is a device array)
        tm.record("crush_do_rule_batch",
                  "pallas" if uses_pallas else "xla",
                  time.perf_counter() - t0,
                  bytes_in=int(getattr(xs, "nbytes", 0) or 0),
                  bytes_out=int(getattr(out, "nbytes", 0) or 0),
                  compiled=compiled)
    return out


def _launch_rule_fn(cm, cached, xs, numrep, weightvec) -> jnp.ndarray:
    import contextlib

    vf, max_width, engine_mode = cached

    # the limb engine traces WITHOUT x64 (its whole point); weightvec
    # semantics survive the int32 clamp because is_out only compares
    # weights below 0x10000 (values above mean "always in")
    ctx = enable_x64() if engine_mode != "limb" else contextlib.nullcontext()
    with ctx:
        with stage("crush.launch"):
            xs_np = np.asarray(xs, dtype=np.int32)
            if engine_mode == "limb":
                weightvec = jnp.asarray(
                    np.minimum(
                        np.asarray(weightvec, dtype=np.int64), 0x10000
                    ).astype(np.int32)
                )
            else:
                weightvec = jnp.asarray(weightvec, dtype=jnp.int64)
        N = xs_np.shape[0]
        # chunk by LANES (N x max step width), not raw N: a multi-choose
        # step fans each x out to its working-vector width
        chunk_n = max(1, _BATCH_CHUNK // max_width)

        def padded_width(n: int) -> int:
            # next power of two, capped at chunk_n: bounds compiled-shape
            # count to log2(chunk_n) while never exceeding the lane budget
            # (an uncapped pow2 pad of a non-pow2 chunk_n could launch ~2x
            # _BATCH_CHUNK lanes — the empirical v5e fault boundary)
            p = max(1, 1 << (max(n, 1) - 1).bit_length())
            return chunk_n if p > chunk_n else p

        if N <= chunk_n:
            Np = padded_width(N)
            with stage("crush.launch"):
                out = vf(jnp.asarray(np.resize(xs_np, Np)), weightvec)
                return out[:N] if Np != N else out
        # Large batches run as fixed-size device calls: one launch over
        # >~512k lanes (int64 while-loops) hard-faults the v5e worker, and
        # a single huge launch would also hold the whole [lanes, S]
        # intermediate set live in HBM.  Chunking keeps each launch inside
        # the envelope at ~zero throughput cost (per-x math dwarfs
        # dispatch).
        pieces = []
        for lo in range(0, N, chunk_n):
            part = xs_np[lo : lo + chunk_n]
            # ragged tail: pad to its own (capped) power of two — a shape
            # the small-batch path compiles anyway — not to a full chunk
            width = padded_width(len(part))
            padded = np.resize(part, width)
            with stage("crush.launch"):
                raw = vf(jnp.asarray(padded), weightvec)
            with stage("crush.wait"):
                raw.block_until_ready()  # noqa: CL8 — each chunk's fetch is the deliberate sync
            with stage("crush.d2h"):
                pieces.append(np.asarray(raw)[: len(part)])
        return jnp.asarray(np.concatenate(pieces))
