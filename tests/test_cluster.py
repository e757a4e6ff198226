"""Ring-2 tests: multi-daemon localhost cluster over real sockets
(reference: qa/standalone/erasure-code/test-erasure-code.sh flows +
qa/tasks/thrashosds.py kill/revive; SURVEY.md §4 ring 2).

One module-scoped cluster serves the non-destructive I/O tests; the
kill/revive/recovery and thrash tests build their own so OSD deaths never
leak between tests.
"""
import random
import threading
import time

import pytest

from ceph_tpu.qa.vstart import LocalCluster

pytestmark = pytest.mark.cluster


@pytest.fixture(scope="module")
def cluster():
    # module-scoped by measurement, not oversight: a session-shared
    # cluster (tried in the cephrace PR) kept 9 daemons ticking and
    # scrubbing for the whole 700 s session and slowed the suite by
    # ~100 s — teardown at module end is cheaper than a live cluster
    with LocalCluster(n_mons=3, n_osds=6) as c:
        c.create_ec_pool("ecpool", k=4, m=2)
        c.create_replicated_pool("repl", size=3)
        yield c


@pytest.fixture(scope="module")
def client(cluster):
    return cluster.client()


# -- basic I/O --------------------------------------------------------------

def test_ec_write_read_roundtrip(cluster, client):
    io = client.open_ioctx("ecpool")
    cases = {
        "empty": b"",
        "one": b"x",
        "unaligned": b"0123456789" * 333 + b"zz",  # not a stripe multiple
        "big": bytes(range(256)) * 512,            # 128 KiB
    }
    for oid, data in cases.items():
        io.write_full(oid, data)
    for oid, data in cases.items():
        assert io.read(oid) == data, oid
    # overwrite changes content and version
    io.write_full("one", b"replaced")
    assert io.read("one") == b"replaced"


def test_ec_stat_list_delete(cluster, client):
    io = client.open_ioctx("ecpool")
    io.write_full("doomed", b"d" * 4096)
    st = io.stat("doomed")
    assert st["size"] == 4096
    assert "doomed" in io.list_objects()
    io.remove("doomed")
    assert "doomed" not in io.list_objects()
    with pytest.raises(IOError):
        io.stat("doomed")


def test_ec_partial_read(cluster, client):
    io = client.open_ioctx("ecpool")
    data = bytes(range(256)) * 64
    io.write_full("ranged", data)
    assert io.read("ranged", off=100, length=50) == data[100:150]
    assert io.read("ranged", off=len(data) - 10) == data[-10:]


def test_replicated_pool_io(cluster, client):
    io = client.open_ioctx("repl")
    io.write_full("r1", b"replicated bytes")
    assert io.read("r1") == b"replicated bytes"
    io.remove("r1")
    with pytest.raises(IOError):
        io.read("r1")


def test_mon_command_surface(cluster, client):
    rv, res = client.command({"prefix": "osd dump"})
    assert rv == 0


def test_healthy_writes_trigger_no_recovery_push(cluster, client):
    """Recovery passes run all the time; one whose peer query raced a
    client write must not re-push what the write path already delivered
    (every peer answered current, then took the write's sub-op).  On a
    healthy pool no pass pushes anything."""
    cluster.create_ec_pool("ecquiet", k=4, m=2)
    cluster.wait_clean("ecquiet")
    io = client.open_ioctx("ecquiet")
    for i in range(16):
        io.write_full(f"q{i}", bytes([i]) * 3000)
        io.write(f"q{i}", b"rmw", off=1000)
    time.sleep(1.0)  # a few more passes over the settled pool
    pid = client.pool_id("ecquiet")
    pgs = [pg for osd in cluster.osds.values()
           for pgid, pg in osd.pgs.items() if pgid.startswith(f"{pid}.")]
    assert pgs
    pushed = sum(getattr(pg, "stat_delta_recoveries", 0)
                 + getattr(pg, "stat_backfills", 0) for pg in pgs)
    assert pushed == 0, f"{pushed} recovery rounds on a healthy pool"
    for i in range(16):
        assert io.read(f"q{i}")[999:1004] == bytes([i]) + b"rmw" + bytes([i])


class _WriteBeforePush:
    """Stands in for a PG's lock on its primary: once a recovery pass of
    that PG has queried its peers, a client write lands before the pass
    takes the lock for its push phase, so every such pass races a
    write."""

    def __init__(self, osd, pg, write):
        self.inner, self.pg, self.write = pg.lock, pg, write
        self.armed, self.raced, self.errors = set(), 0, []
        osd._recover_pg = self._pass(osd._recover_pg)
        osd._bg_stage = self._stage(osd._bg_stage)

    def _pass(self, recover):
        def run(pg, *args):
            try:
                return recover(pg, *args)
            finally:
                self.armed.discard((id(pg), threading.get_ident()))
        return run

    def _stage(self, bg_stage):
        def stage(name, *args, **tags):
            bg_stage(name, *args, **tags)
            if name == "recovery_peer":
                self.armed.add((id(self.pg), threading.get_ident()))
        return stage

    def __enter__(self):
        key = (id(self.pg), threading.get_ident())
        if key in self.armed:
            self.armed.discard(key)
            if self.pg.activated_interval == self.pg.interval_start:
                try:
                    self.write(self.raced)
                    self.raced += 1
                except (IOError, ConnectionError) as e:
                    self.errors.append(e)
        return self.inner.__enter__()

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def test_role_backfill_under_steady_writes(cluster, client):
    """Two version-current OSDs swap shard roles (an upmap permutation)
    while a client write lands on the PG between every recovery pass's
    peer query and its push phase: each new role's collection is still
    rebuilt while the writes go on."""
    cluster.create_ec_pool("ecswap", k=2, m=2, pg_num=1)
    cluster.wait_clean("ecswap")
    io = client.open_ioctx("ecswap")
    blobs = {f"w{i}": bytes([i]) * 3000 for i in range(12)}
    for oid, data in blobs.items():
        io.write_full(oid, data)
    pid = client.pool_id("ecswap")
    m = cluster._leader().osdmon.osdmap
    _up, _upp, acting, primary = m.pg_to_up_acting_osds(pid, 0)
    i, j = [s for s, o in enumerate(acting) if o != primary][:2]
    a, b = acting[i], acting[j]
    x = next(o for o in cluster.osds if o not in acting)
    posd = cluster.osds[primary]
    pg = posd.pgs[f"{pid}.0"]
    racer = _WriteBeforePush(
        posd, pg, lambda n: io.write_full("hot", n.to_bytes(4, "little")
                                          * 750))
    pg.lock = racer
    try:
        rv, res = cluster.mon_command({
            "prefix": "osd pg-upmap-items", "pool": pid, "ps": 0,
            "mappings": [[a, x], [b, a], [x, b]]})
        assert rv == 0, (rv, res)

        def roles_filled() -> bool:
            m = cluster._leader().osdmon.osdmap
            now = m.pg_to_up_acting_osds(pid, 0)[2]
            if (now[i], now[j]) != (b, a):
                return False
            for shard, osd in ((i, b), (j, a)):
                try:
                    held = set(cluster.osds[osd].store.list_objects(
                        f"{pid}.0s{shard}"))
                except Exception:
                    return False
                if not set(blobs) <= held:
                    return False
            return True

        deadline = time.monotonic() + 30.0
        while not roles_filled():
            assert time.monotonic() < deadline, (
                f"shard roles {i}/{j} not backfilled under writes "
                f"({racer.raced} raced passes)")
            time.sleep(0.1)
    finally:
        pg.lock = racer.inner
        del posd._recover_pg, posd._bg_stage
    assert racer.raced and not racer.errors, (racer.raced, racer.errors)
    cluster.wait_clean("ecswap")
    for oid, data in blobs.items():
        assert io.read(oid) == data, oid


# -- failure / recovery -----------------------------------------------------

def _fill(io, prefix, n, size=3000):
    blobs = {}
    for i in range(n):
        oid = f"{prefix}{i}"
        blobs[oid] = bytes([(i * 7 + j) % 256 for j in range(size)])
        io.write_full(oid, blobs[oid])
    return blobs


def test_kill_degraded_read_and_delta_recovery():
    with LocalCluster(n_mons=1, n_osds=6) as c:
        c.create_ec_pool("ec", k=4, m=2)
        io = c.client().open_ioctx("ec")
        blobs = _fill(io, "pre", 6)

        c.kill_osd(4)
        # degraded read: decode path must reconstruct missing chunks
        for oid, data in blobs.items():
            assert io.read(oid) == data, f"degraded read {oid}"

        # degraded writes while the OSD is down+out
        c.mark_osd_down_out(4)
        blobs.update(_fill(io, "down", 4))

        c.revive_osd(4)
        c.mark_osd_in_up(4)
        c.wait_clean("ec", timeout=60)

        # the revived OSD's outage fits inside the pg_log: primaries must
        # have taken the delta path, not backfill
        deltas = sum(
            getattr(pg, "stat_delta_recoveries", 0)
            for osd in c.osds.values()
            for pg in osd.pgs.values()
        )
        backfills = sum(
            getattr(pg, "stat_backfills", 0)
            for osd in c.osds.values()
            for pg in osd.pgs.values()
        )
        assert deltas > 0, "no delta recovery happened"
        assert backfills == 0, "short outage must not trigger backfill"

        for oid, data in blobs.items():
            assert io.read(oid) == data, f"post-recovery read {oid}"


def test_recovered_shard_holds_real_data():
    """After recovery the revived OSD must hold decodable chunk bytes —
    guards the push path end-to-end (a no-op recovery that only bumps
    versions would pass wait_clean but fail here)."""
    with LocalCluster(n_mons=1, n_osds=6) as c:
        c.create_ec_pool("ec", k=4, m=2)
        io = c.client().open_ioctx("ec")
        c.kill_osd(2)
        c.mark_osd_down_out(2)
        blobs = _fill(io, "obj", 5)
        c.revive_osd(2)
        c.mark_osd_in_up(2)
        c.wait_clean("ec", timeout=60)
        osd2 = c.osds[2]
        stored = 0
        for cid in osd2.store.list_collections():
            stored += sum(
                1 for o in osd2.store.list_objects(cid)
                if not o.startswith("_")
            )
        # osd2 is in the acting set of at least one of the 5 objects' PGs
        # with overwhelming probability (6 OSDs, 4+2 = all of them acting)
        assert stored > 0, "revived OSD holds no recovered chunks"
        for oid, data in blobs.items():
            assert io.read(oid) == data


def test_backfill_when_log_trimmed():
    """Outage longer than the pg_log: the primary must fall back to full
    backfill (reference: PGLog tail passed → backfill)."""
    from ceph_tpu.osd.pg_log import PGLog

    # the limit is a def-time default — patch the default tuple itself
    old = PGLog.__init__.__defaults__
    PGLog.__init__.__defaults__ = (4,)  # tiny log → outage outruns it
    try:
        with LocalCluster(n_mons=1, n_osds=6) as c:
            c.create_ec_pool("ec", k=4, m=2, pg_num=1)
            io = c.client().open_ioctx("ec")
            io.write_full("seed", b"s" * 2000)
            _primary_peer(c, "ec")  # kills a non-primary acting member
            blobs = _fill(io, "trim", 8)  # 8 writes > log limit 4
            victim = c._last_killed
            c.revive_osd(victim)
            c.wait_clean("ec", timeout=60)
            backfills = sum(
                getattr(pg, "stat_backfills", 0)
                for osd in c.osds.values()
                for pg in osd.pgs.values()
            )
            assert backfills > 0, "trimmed log must force backfill"
            # the backfilled peer's log window must be SEALED (head ==
            # tail): it cannot vouch entry-by-entry for anything below its
            # version, so covers() must say no if it later becomes primary
            revived = c.osds[victim]
            sealed = [
                pg for pg in revived.pgs.values()
                if pg.version > 0 and pg.log.tail == pg.log.head == pg.version
            ]
            assert sealed, "backfilled peer kept a lying log window"
            for oid, data in blobs.items():
                assert io.read(oid) == data
    finally:
        PGLog.__init__.__defaults__ = old


def test_backfill_propagates_deletions():
    """An object deleted while a peer was away — with the delete trimmed
    out of the log — must NOT survive on the revived peer: the backfill
    pushes data-less deletes for the target's stale extras (resurrection
    guard)."""
    from ceph_tpu.osd.pg_log import PGLog

    old = PGLog.__init__.__defaults__
    PGLog.__init__.__defaults__ = (4,)
    try:
        with LocalCluster(n_mons=1, n_osds=4) as c:
            c.create_replicated_pool("rp", size=3, pg_num=1)
            io = c.client().open_ioctx("rp")
            io.write_full("victim", b"gone soon")
            killed = _primary_peer(c, "rp")
            io.remove("victim")
            _fill(io, "churn", 8)  # trim the delete out of the log
            c.revive_osd(killed)
            c.wait_clean("rp", timeout=60)
            # the revived peer's store must NOT hold the deleted object
            revived = c.osds[killed]
            import time as _t

            deadline = _t.time() + 30
            while _t.time() < deadline:
                held = [
                    o for cid in revived.store.list_collections()
                    for o in revived.store.list_objects(cid)
                    if o == "victim"
                ]
                if not held:
                    break
                _t.sleep(0.5)
            assert not held, "deleted object resurrected on revived peer"
            assert "victim" not in io.list_objects()
    finally:
        PGLog.__init__.__defaults__ = old


def _primary_peer(c, pool_name):
    """Kill target: a non-primary acting member of the pool's only PG (so
    the primary keeps serving and logging writes).  The kill is also
    pushed as a map change — without it, writes stall on the dead shard's
    sub-op until heartbeat detection lands (~6s of nondeterminism)."""
    m = c._leader().osdmon.osdmap
    pid = next(i for i, p in m.pools.items() if p.name == pool_name)
    _up, _upp, acting, primary = m.pg_to_up_acting_osds(pid, 0)
    victim = next(o for o in acting if o >= 0 and o != primary)
    c._last_killed = victim
    c.kill_osd(victim)
    rv, res = c.mon_command({"prefix": "osd down", "id": victim})
    assert rv == 0, (rv, res)
    return victim


@pytest.mark.slow   # ~34 s soak; the seeded cephrace thrash gate covers
# the short-thrash path in tier-1 (tier-1 runs under a hard 870 s cap)
def test_thrash_soak():
    """Randomized kill/revive during writes — zero data loss (reference:
    qa/tasks/thrashosds.py).  Bounded to ~4 cycles to stay CI-sized."""
    rng = random.Random(1234)
    with LocalCluster(n_mons=1, n_osds=6) as c:
        c.create_ec_pool("ec", k=4, m=2)
        io = c.client().open_ioctx("ec")
        blobs = {}
        down: int | None = None
        for cycle in range(4):
            blobs.update(_fill(io, f"c{cycle}_", 3, size=1500))
            if down is None:
                down = rng.choice(sorted(c.osds))
                c.kill_osd(down)
                # push the map change rather than waiting out heartbeat
                # grace (the thrasher shortens mon grace the same way)
                c.mark_osd_down_out(down)
            else:
                c.revive_osd(down)
                c.mark_osd_in_up(down)
                down = None
            # reads stay correct mid-thrash
            for oid in rng.sample(sorted(blobs), min(4, len(blobs))):
                assert io.read(oid) == blobs[oid], f"mid-thrash {oid}"
        if down is not None:
            c.revive_osd(down)
            c.mark_osd_in_up(down)
        c.wait_clean("ec", timeout=90)
        for oid, data in blobs.items():
            assert io.read(oid) == data, f"final read {oid}"


def test_client_resend_on_primary_change():
    """Objecter must re-target when the primary moves (op_submit resend
    rule; reference: Objecter::_calc_target epoch change)."""
    with LocalCluster(n_mons=1, n_osds=6) as c:
        c.create_ec_pool("ec", k=4, m=2, pg_num=1)
        io = c.client().open_ioctx("ec")
        io.write_full("moving", b"m" * 2048)
        m = c._leader().osdmon.osdmap
        pid = next(i for i, p in m.pools.items() if p.name == "ec")
        _up, _upp, _acting, primary = m.pg_to_up_acting_osds(pid, 0)
        c.kill_osd(primary)
        c.mark_osd_down_out(primary)
        # next op must discover the new primary via the map subscription
        assert io.read("moving") == b"m" * 2048


def test_osd_restart_persists_pg_state():
    """An OSD that restarts on its own store must come back with its PG
    versions (WAL/omap persistence through PGState reload)."""
    with LocalCluster(n_mons=1, n_osds=6) as c:
        c.create_ec_pool("ec", k=4, m=2)
        io = c.client().open_ioctx("ec")
        blobs = _fill(io, "persist", 4)
        victim = sorted(c.osds)[0]
        before = {
            pgid: pg.version for pgid, pg in c.osds[victim].pgs.items()
            if pg.version > 0
        }
        c.kill_osd(victim)
        osd = c.revive_osd(victim)
        after = {
            pgid: pg.version for pgid, pg in osd.pgs.items()
            if pgid in before
        }
        for pgid, v in before.items():
            assert after.get(pgid, 0) >= v, (pgid, before, after)
        c.wait_clean("ec", timeout=60)
        for oid, data in blobs.items():
            assert io.read(oid) == data


def test_user_xattrs(cluster, client):
    """librados xattr surface: set/get/rm, replicated to shards
    (reference: rados_setxattr/getxattrs).  Non-destructive half; the
    primary-kill half builds its own cluster below."""
    io = client.open_ioctx("ecpool")
    io.write_full("attrobj", b"body" * 300)
    io.set_xattr("attrobj", "owner", b"alice")
    io.set_xattr("attrobj", "tag", b"\x00\xffbinary")
    assert io.get_xattrs("attrobj") == {
        "owner": b"alice", "tag": b"\x00\xffbinary"
    }
    io.set_xattr("attrobj", "owner", b"bob")  # overwrite
    assert io.get_xattr("attrobj", "owner") == b"bob"
    io.rm_xattr("attrobj", "tag")
    assert io.get_xattrs("attrobj") == {"owner": b"bob"}
    with pytest.raises(IOError):
        io.set_xattr("no-such-object", "x", b"y")


def test_user_xattrs_survive_primary_change():
    """Every shard carries user xattrs, so a remapped primary still
    serves them (and a removal never resurrects through recovery)."""
    from ceph_tpu.osd.osdmap import object_ps

    with LocalCluster(n_mons=1, n_osds=6) as c:
        c.create_ec_pool("ec", k=4, m=2)
        io = c.client().open_ioctx("ec")
        io.write_full("attrobj", b"body" * 300)
        io.set_xattr("attrobj", "owner", b"bob")
        io.set_xattr("attrobj", "gone", b"soon")
        io.rm_xattr("attrobj", "gone")
        m = c._leader().osdmon.osdmap
        pid = next(i for i, p in m.pools.items() if p.name == "ec")
        ps = object_ps("attrobj", m.pools[pid].pg_num)
        _up, _upp, _acting, primary = m.pg_to_up_acting_osds(pid, ps)
        c.kill_osd(primary)
        c.mark_osd_down_out(primary)
        assert io.get_xattrs("attrobj") == {"owner": b"bob"}
        c.revive_osd(primary)
        c.mark_osd_in_up(primary)
        c.wait_clean("ec", timeout=60)
        assert io.get_xattrs("attrobj") == {"owner": b"bob"}


def test_eagain_fails_fast_when_min_size_unreachable():
    """Advisor r3 / r4 verdict #7: when the client's own map shows the
    PG below min_size, the EAGAIN retry loop must fail fast (one map
    wait), not sit out the full 60 s patience."""
    with LocalCluster(n_mons=1, n_osds=4) as c:
        c.create_ec_pool("ec", k=2, m=1)  # min_size 2... size 3
        io = c.client().open_ioctx("ec")
        io.write_full("fast-fail", b"x" * 2000)
        # take enough OSDs down+out that min_size is unreachable; the
        # map reflects it, so the client can prove futility
        m = c._leader().osdmon.osdmap
        pid = next(i for i, p in m.pools.items() if p.name == "ec")
        from ceph_tpu.osd.osdmap import object_ps

        ps = object_ps("fast-fail", m.pools[pid].pg_num)
        _up, _upp, acting, _pri = m.pg_to_up_acting_osds(pid, ps)
        keep = acting[0]
        # leave ONE live OSD: min_size (2) is then provably unreachable
        # even after CRUSH remaps around the out OSDs
        for osd in sorted(set(c.osds) - {keep}):
            c.kill_osd(osd)
            c.mark_osd_down_out(osd)
        t0 = time.monotonic()
        with pytest.raises((IOError, ConnectionError)):
            io.write_full("fast-fail", b"y" * 2000)
        elapsed = time.monotonic() - t0
        assert elapsed < 30.0, (
            f"min_size-unreachable write took {elapsed:.1f}s; "
            f"should fail fast, not wait out the patience"
        )


def test_stray_location_cache_skips_repeat_probes():
    """Advisor r4 verdict #7: a repeat degraded read of the same PG must
    hit the per-PG stray-location cache instead of re-walking probes."""
    with LocalCluster(n_mons=1, n_osds=6) as c:
        c.create_ec_pool("ec", k=4, m=2)
        io = c.client().open_ioctx("ec")
        blobs = _fill(io, "cache", 4)
        # remap by taking one OSD down+out: acting permutes, some shards
        # live only at their old (now non-acting) holders
        c.kill_osd(3)
        c.mark_osd_down_out(3)
        for oid, data in blobs.items():
            assert io.read(oid) == data
        probes_first = sum(
            o.logger.get("stray_probes") or 0 for o in c.osds.values()
        )
        for oid, data in blobs.items():
            assert io.read(oid) == data
        probes_second = sum(
            o.logger.get("stray_probes") or 0 for o in c.osds.values()
        )
        # the second pass may probe a little (recovery may be moving
        # data concurrently) but must not re-pay the full first-pass walk
        assert probes_second - probes_first <= probes_first / 2, (
            probes_first, probes_second
        )
