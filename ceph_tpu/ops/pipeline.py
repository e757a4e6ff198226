"""Host<->device pipelining for stripe-batch streams (SURVEY.md §2.9
"pipeline parallelism" analog: the reference overlaps its write pipeline
stages; the TPU equivalent is double-buffering host->device DMA against
kernel compute).

`stream_encode` drives a sequence of host batches through the production
GF apply (`ops.bitplane.apply_matrix_jax`) with at most two batches
resident: while the device computes parity for batch i, batch i+1's
transfer is already in flight (the `jnp.asarray` commit and the kernel
launch are both async under JAX's dispatch model; the np.asarray fetch
of result i-1 is the only sync point and it overlaps the later batches'
work).

The input is consumed as a true ITERATOR: a long traffic run (the
write-batcher's multi-batch bursts, bench soaks) holds at most two
input batches of host memory at any moment, never the whole stream.
"""
from __future__ import annotations

import time

import numpy as np

from ..common.kernel_telemetry import TELEMETRY
from ..common.tracer import stage


def stream_encode(mat: np.ndarray, batches, mat_key: str | None = None):
    """Encode an iterable of [k, L] host batches; returns the list of
    host parity arrays, bit-identical to the per-op path.

    `batches` may be any iterable, including a one-shot generator; it is
    pulled lazily, one batch ahead of the compute, so the stream's
    host-memory high-water mark is two batches regardless of length.
    `mat_key`: the caller's precomputed matrix_digest of `mat`.

    Telemetry: one `stream_encode` record per stream — the np.asarray
    fetches make this a true sync point, so the record carries an honest
    achieved GiB/s for the whole double-buffered pipeline and counts the
    transfer+fetch host-copy volume."""
    import jax.numpy as jnp

    from .bitplane import apply_matrix_jax, current_backend

    tm = TELEMETRY
    t_start = time.perf_counter() if tm.enabled else 0.0
    bytes_in = 0
    mat = np.ascontiguousarray(mat, dtype=np.uint8)

    def commit(host):
        with stage("gf.h2d"):
            return jnp.asarray(host, dtype=jnp.uint8)

    it = iter(batches)
    first = next(it, None)
    if first is None:
        return []
    outs = []
    pending = None  # device result of the previous batch, not yet fetched
    nxt = commit(first)
    while nxt is not None:
        cur = nxt
        bytes_in += int(cur.nbytes)
        # launch compute first (async), THEN start the next DMA so the
        # copy engine and the cores overlap
        res = apply_matrix_jax(mat, cur, mat_key=mat_key)
        upcoming = next(it, None)
        nxt = commit(upcoming) if upcoming is not None else None
        if pending is not None:
            # fetch the previous result; keeps two batches live
            outs.append(np.asarray(pending))
        pending = res
    outs.append(np.asarray(pending))
    if tm.enabled:
        bytes_out = sum(int(o.nbytes) for o in outs)
        tm.record("stream_encode", current_backend(),
                  time.perf_counter() - t_start,
                  bytes_in=bytes_in, bytes_out=bytes_out, synced=True,
                  host_copy_bytes=bytes_in + bytes_out)
    return outs
