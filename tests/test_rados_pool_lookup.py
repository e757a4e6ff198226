"""Rados.pool_id against a client map that predates the pool: the lookup
asks the mon for its epoch and waits, briefly, for that map before it
calls the name unknown."""
from types import SimpleNamespace

import pytest

from ceph_tpu.client.rados import Rados


def _map(epoch, **pools):
    return SimpleNamespace(epoch=epoch, pools={
        pid: SimpleNamespace(name=name) for name, pid in pools.items()})


class _FakeMonClient:
    def __init__(self, osdmap, mon_epoch, newer=None, rv=0):
        self.osdmap, self.mon_epoch, self.newer, self.rv = (
            osdmap, mon_epoch, newer, rv)
        self.commands, self.waits = [], []

    def command(self, cmd, timeout=10.0):
        self.commands.append((cmd["prefix"], timeout))
        return self.rv, {"epoch": self.mon_epoch}

    def wait_for_osdmap(self, min_epoch=1, timeout=10.0):
        self.waits.append((min_epoch, timeout))
        if self.newer is None or self.newer.epoch < min_epoch:
            raise TimeoutError(f"no osdmap >= e{min_epoch}")
        self.osdmap = self.newer
        return self.newer


def _rados(mc):
    r = Rados.__new__(Rados)
    r.mc = mc
    return r


def test_known_pool_costs_no_mon_round_trip():
    mc = _FakeMonClient(_map(5, rbd=1), mon_epoch=5)
    assert _rados(mc).pool_id("rbd") == 1
    assert mc.commands == [] and mc.waits == []


def test_pool_created_since_the_client_map_is_found():
    mc = _FakeMonClient(_map(5, rbd=1), mon_epoch=7,
                        newer=_map(7, rbd=1, rgw_data=2))
    assert _rados(mc).pool_id("rgw_data") == 2
    assert mc.commands == [("osd dump", 3.0)]
    assert mc.waits == [(7, 3.0)]


@pytest.mark.parametrize("mon_epoch, newer, rv", [
    (5, None, 0),                       # the mon knows no newer map
    (7, None, 0),                       # the newer map never arrives
    (7, _map(7, rbd=1), 0),             # it arrives without the pool
    (7, None, -110),                    # the mon does not answer
], ids=["current", "map_timeout", "absent", "mon_down"])
def test_unknown_pool_raises_keyerror(mon_epoch, newer, rv):
    mc = _FakeMonClient(_map(5, rbd=1), mon_epoch, newer, rv)
    with pytest.raises(KeyError):
        _rados(mc).pool_id("nope")
    assert all(t <= 3.0 for _p, t in mc.commands)
    assert all(t <= 3.0 for _e, t in mc.waits)
