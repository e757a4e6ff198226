"""Plain CRUSH reference for the placement cells: straw2 buckets, a
`take root / chooseleaf firstn 0 type host / emit` rule, and the OSDMap
steps around it (pg -> pps seed, reweight, up set, primary).

Written from Ceph's documented mapper.c, hash.c and OSDMap semantics
under the optimal (jewel) tunables: choose_total_tries 50 (the descent
makes choose_total_tries + 1 attempts), chooseleaf_descend_once 1 (a
recursive leaf choice gets one attempt and a rejection retries the whole
descent), chooseleaf_vary_r 1, chooseleaf_stable 1, no local retries.
It is vectorised over PGs with numpy and imports nothing of the system
under test.

crush_ln's tables follow crush_ln_table.h's documented formulas
(RH = 2^56/index1 rounded up, LH = 2^48 log2(index1/256), LL =
2^48 log2(1 + i/2^15), both rounded to nearest).
"""
from __future__ import annotations

import math
from functools import lru_cache

import numpy as np

ITEM_NONE = 0x7FFFFFFF
SEED = 1315423911


def _mix(a, b, c):
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(13))
    b = b - c; b = b - a; b = b ^ (a << np.uint32(8))
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(13))
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(12))
    b = b - c; b = b - a; b = b ^ (a << np.uint32(16))
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(5))
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(3))
    b = b - c; b = b - a; b = b ^ (a << np.uint32(10))
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(15))
    return a, b, c


def _u32(v):
    return np.asarray(v).astype(np.int64).astype(np.uint32)


def hash2(a, b):
    """crush_hash32_rjenkins1_2."""
    with np.errstate(over="ignore"):
        a, b = _u32(a), _u32(b)
        h = np.uint32(SEED) ^ a ^ b
        x, y = np.uint32(231232), np.uint32(1232)
        a, b, h = _mix(a, b, h)
        x, a, h = _mix(x, a, h)
        b, y, h = _mix(b, y, h)
        return h


def hash3(a, b, c):
    """crush_hash32_rjenkins1_3 (the straw2 draw)."""
    with np.errstate(over="ignore"):
        a, b, c = _u32(a), _u32(b), _u32(c)
        h = np.uint32(SEED) ^ a ^ b ^ c
        x, y = np.uint32(231232), np.uint32(1232)
        a, b, h = _mix(a, b, h)
        c, x, h = _mix(c, x, h)
        y, a, h = _mix(y, a, h)
        b, x, h = _mix(b, x, h)
        y, c, h = _mix(y, c, h)
        return h


@lru_cache(maxsize=1)
def ln_table() -> np.ndarray:
    """crush_ln(u) - 2^48 for every 16-bit u (the straw2 draw's log)."""
    rh_lh = {}
    for index1 in range(256, 513, 2):
        rh_lh[index1] = (-((-(1 << 56)) // index1),
                         round(float(1 << 48) * math.log2(index1 / 256.0)))
    ll = [round(float(1 << 48) * math.log2(1.0 + i / 32768.0))
          for i in range(256)]
    out = np.zeros(0x10000, dtype=np.int64)
    for u in range(0x10000):
        x = u + 1
        iexpon = 15
        if not x & 0x18000:
            bits = (32 - (x & 0x1FFFF).bit_length()) - 16
            x <<= bits
            iexpon = 15 - bits
        index1 = (x >> 8) << 1
        rh, lh = rh_lh[index1]
        index2 = ((x * rh) >> 48) & 0xFF
        out[u] = (iexpon << 44) + ((lh + ll[index2]) >> 4) - (1 << 48)
    return out


def straw2(x, r, items, weights):
    """Winner of a straw2 bucket for each lane: x, r [A]; item ids and
    weights [A, S] -> [A] column of the chosen item.  The largest draw
    trunc(ln(u) / w) wins, the first on ties; weight 0 never wins."""
    u = hash3(x[:, None], items, r[:, None]) & np.uint32(0xFFFF)
    ln = ln_table()[u.astype(np.int64)]
    w = np.maximum(weights, 1)
    draw = -((-ln) // w)  # ln <= 0: C's truncating division
    draw = np.where(weights > 0, draw, np.iinfo(np.int64).min)
    return np.argmax(draw, axis=1)


def is_out(reweight: np.ndarray, osd, x):
    w = reweight[osd]
    h = hash2(x, osd) & np.uint32(0xFFFF)
    return np.where(w >= 0x10000, False,
                    np.where(w == 0, True, h.astype(np.int64) >= w))


class HostTree:
    """root -> hosts -> OSDs, every bucket straw2, as the configuration
    states it: host h is bucket id -(h + 2) holding OSDs
    h*per_host .. h*per_host + per_host - 1, the root is bucket -1."""

    def __init__(self, hosts: int, per_host: int, osd_weight: int = 0x10000):
        self.hosts = hosts
        self.per_host = per_host
        self.host_ids = -(np.arange(hosts, dtype=np.int64) + 2)
        self.host_weight = np.full(hosts, per_host * osd_weight, np.int64)
        self.osd_weight = np.full(hosts * per_host, osd_weight, np.int64)

    def chooseleaf_firstn(self, xs: np.ndarray, numrep: int,
                          reweight: np.ndarray,
                          total_tries: int = 50) -> np.ndarray:
        """[N] inputs -> [N, numrep] OSDs, ITEM_NONE past a short result."""
        n = len(xs)
        tries = total_tries + 1
        hosts = np.full((n, numrep), ITEM_NONE, np.int64)
        leaves = np.full((n, numrep), ITEM_NONE, np.int64)
        outpos = np.zeros(n, np.int64)
        per = self.per_host
        lanes_all = np.arange(n)
        for rep in range(numrep):
            active = lanes_all.copy()
            ftotal = np.zeros(n, np.int64)
            while active.size:
                x = xs[active]
                r = rep + ftotal[active]
                hidx = straw2(
                    x, r,
                    np.broadcast_to(self.host_ids, (len(x), self.hosts)),
                    np.broadcast_to(self.host_weight, (len(x), self.hosts)))
                host = self.host_ids[hidx]
                pos = outpos[active]
                before = np.arange(numrep)[None, :] < pos[:, None]
                collide = ((hosts[active] == host[:, None]) & before).any(1)
                # the recursive leaf choice, one attempt
                # (chooseleaf_descend_once), r' = r >> (vary_r - 1)
                items = hidx[:, None] * per + np.arange(per)[None, :]
                osd = np.take_along_axis(
                    items, straw2(x, r, items, self.osd_weight[items])[:, None],
                    axis=1)[:, 0]
                leaf_collide = ((leaves[active] == osd[:, None])
                                & before).any(1)
                reject = leaf_collide | is_out(reweight, osd, x)
                ok = ~collide & ~reject
                done = active[ok]
                hosts[done, outpos[done]] = host[ok]
                leaves[done, outpos[done]] = osd[ok]
                outpos[done] += 1
                failed = active[~ok]
                ftotal[failed] += 1
                active = failed[ftotal[failed] < tries]
        return leaves


def stable_mod(x: np.ndarray, b: int, bmask: int) -> np.ndarray:
    lo = x & bmask
    return np.where(lo < b, lo, x & (bmask >> 1))


def pool_raw(tree: HostTree, pool_id: int, pg_num: int, size: int,
             reweight: np.ndarray) -> np.ndarray:
    """CRUSH's [pg_num, size] OSDs for every PG of a replicated pool:
    pg -> pps seed (ceph_stable_mod, then hash with the pool id), then
    the rule."""
    mask = (1 << (pg_num - 1).bit_length()) - 1
    ps = np.arange(pg_num, dtype=np.int64)
    pps = hash2(stable_mod(ps, pg_num, mask), pool_id).astype(np.int64)
    return tree.chooseleaf_firstn(pps, size, reweight)


def up_set(raw: np.ndarray, down: int | None) -> tuple[np.ndarray, np.ndarray]:
    """(up [pg_num, size], up_primary [pg_num]) of a replicated pool with
    no upmaps and default primary affinity: CRUSH's OSDs minus the down
    one, compacted left and filled with ITEM_NONE; the primary is the
    first up OSD."""
    up = raw.copy()
    if down is not None:
        gone = up == down
        order = np.argsort(gone, axis=1, kind="stable")
        up = np.take_along_axis(up, order, axis=1)
        up[np.take_along_axis(gone, order, axis=1)] = ITEM_NONE
    return up, up[:, 0].copy()
