"""Shared on-device timing for encode benchmarks.

Honest throughput numbers need (a) iterations chained on-device with a
data dependency, so nothing can be elided, and (b) a scalar-fetch barrier,
so the timer stops when the device is done and not when the launch is
queued.  Both bench.py and ceph_tpu.bench.ec_bench use this one
implementation so the subtleties can't drift apart.
"""
from __future__ import annotations

import time
from functools import partial

import numpy as np


def make_chained_encode(coding: np.ndarray, kernel: str = "xla"):
    """(loop, prep): `prep(chunks)` maps a [k, L] host array to the device
    layout the kernel wants; `loop(x, iters)` runs `iters` dependent
    encodes of it.  kernel: 'xla' (ops.bitplane) or 'pallas' (ops.pallas_gf).
    """
    import jax
    import jax.numpy as jnp

    coding = np.ascontiguousarray(coding, dtype=np.uint8)
    rows, n = coding.shape
    if kernel == "pallas":
        from ..ops.pallas_gf import (
            _apply_grouped,
            _kron_matrices,
            _pick_group,
            _pick_layout,
        )

        if rows > n:
            raise ValueError("chained pallas bench needs rows <= n")
        G = _pick_group(rows, n)
        tile = _pick_layout(rows, n, G)
        Bk, Pk = _kron_matrices(coding.tobytes(), coding.shape, G)
        B = jnp.asarray(Bk)
        P = jnp.asarray(Pk, jnp.bfloat16)

        def prep(chunks: np.ndarray):
            # pad to a whole number of G*tile segments.  Padded bytes are
            # computed but not counted by callers, so throughput is
            # understated.
            chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
            pad = (-chunks.shape[1]) % (G * tile)
            if pad:
                chunks = np.pad(chunks, ((0, 0), (0, pad)))
            return jnp.asarray(chunks)

        def apply_fn(x):
            return _apply_grouped(B, P, x, rows, n, G, tile, False)

    else:
        from ..ops.bitplane import _apply_bitmatrix, _bitmatrix_for

        B = _bitmatrix_for(coding)

        def prep(chunks: np.ndarray):
            return jnp.asarray(np.ascontiguousarray(chunks, dtype=np.uint8))

        def apply_fn(x):
            return _apply_bitmatrix(B, x)

    @partial(jax.jit, static_argnames=("iters",))
    def loop(x, iters):
        def body(_, carry):
            parity = apply_fn(carry)
            return carry.at[:rows].set(carry[:rows] ^ parity)

        return jax.lax.fori_loop(0, iters, body, x)

    return loop, prep


def time_chained_encode(
    coding: np.ndarray, chunks: np.ndarray, iterations: int, kernel: str = "xla",
    subtract_overhead: bool = False, repeats: int = 1,
) -> float:
    """Seconds for `iterations` chained encodes of chunks [k, L].

    subtract_overhead: measure a 1-iteration run and subtract it, returning
    per-iteration seconds * iterations of pure compute (used by bench.py for
    the headline number); otherwise returns the raw wall time of the loop
    (used by the CLI, matching the reference harness's inclusive timing).
    """
    loop, prep = make_chained_encode(coding, kernel)
    x = prep(np.asarray(chunks))
    # warm BOTH computations used in the timed region (loop + scalar fetch):
    # compilation must not land in the timing
    np.asarray(loop(x, 1)[0, 0])
    np.asarray(loop(x, iterations)[0, 0])
    best_t1 = best_tN = float("inf")
    for _ in range(max(1, repeats)):
        if subtract_overhead:
            t0 = time.perf_counter()
            np.asarray(loop(x, 1)[0, 0])
            best_t1 = min(best_t1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(loop(x, iterations)[0, 0])  # scalar fetch = true barrier
        best_tN = min(best_tN, time.perf_counter() - t0)
    # Subtract the 1-iter run (dispatch + fetch overhead) only when the
    # chained run clearly dominates it.  For tiny per-iteration compute
    # (e.g. a [1, 4] decode-matrix apply) both runs are overhead + noise
    # and naive subtraction can go negative — fall back to the raw
    # inclusive time, which understates rather than corrupts.
    if subtract_overhead and best_tN > best_t1 * 1.05:
        per = (best_tN - best_t1) / (iterations - 1)
        return per * iterations
    return best_tN
