"""Operations and bytes the benchmark's kernels need, from shapes alone,
and the table of peaks they are held against."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of `device_kind`; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def gf_apply_bytes(rows: int, n: int, length: int) -> int:
    """HBM bytes a GF(2^8) matrix apply [rows, n] x [n, length] needs at
    the least: each of the n input rows read once and each of the rows
    output rows written once, unpadded.  Whatever implements the apply
    (bit-plane matmul, table lookups, a later kernel) moves no fewer."""
    return (n + rows) * length


def gf_apply_ops(rows: int, n: int, length: int) -> int:
    """8-bit operations the apply needs as one GF(2) bit-matrix product:
    a multiply and an add for each of the (rows*8) x (n*8) bit pairs of
    every byte column."""
    return 2 * (rows * 8) * (n * 8) * length


def gf_apply_min_s(rows: int, n: int, length: int, device_kind: str) -> float:
    """The least time the chip could take for the apply: the larger of
    its bytes over peak HBM bandwidth and its operations over the int8
    peak.  For every shape the cells use the bytes bound."""
    pk = peaks(device_kind)
    return max(gf_apply_bytes(rows, n, length) / pk["hbm_bytes_per_s"],
               gf_apply_ops(rows, n, length) / pk["int8_ops_per_s"])
