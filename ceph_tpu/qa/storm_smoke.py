"""cephstorm CI smoke: a seeded 250-stub failure storm end to end
(qa/ci_gate.sh step 13).

One process, no shortcuts on the control plane:

1. a :class:`~ceph_tpu.qa.storm.StormCluster` — 250 stub OSDs across 4
   racks under a REAL monitor + mgr (every kill/revive/reweight is a
   committed paxos proposal; health checks come from the real digest
   pipeline);
2. a seeded :class:`~ceph_tpu.qa.storm.StormPlanner` storm — kill and
   revive waves (single OSDs and whole racks), a recv-drop rack
   netsplit, reweight remap churn, all under 2-tenant traffic from
   ``bench/traffic.py``'s generators;
3. quiesce, then EVERY :class:`StormInvariantChecker` gate: no acked
   write lost, all PGs clean, forecast-vs-observed remap churn within
   10%, bounded controller oscillation, QoS class conservation, health
   raise-and-clear symmetry, and bit-identical replay (same seed =>
   same event log + ``plan_digest``);
4. a bare-map remap storm (:func:`run_remap_storm`) cross-checking the
   batched mapper against the scalar reference on a PG sample.

Exit 0 on success; 1 with a `problems` list otherwise.  Prints one JSON
summary on stdout (the gate archives it as ``storm_smoke.json``).
"""
from __future__ import annotations

import json
import sys
import time

SEED = 18
N_STUBS = 250
RACKS = 4
EVENTS = 160
PG_NUM = 32
POOL = "stormdata"


def main() -> int:
    from .storm import (
        StormCluster,
        StormInvariantChecker,
        StormPlanner,
        run_remap_storm,
    )

    problems: list[str] = []
    summary: dict = {"seed": SEED, "n_stubs": N_STUBS, "events": EVENTS}
    t0 = time.monotonic()
    try:
        with StormCluster(n_stubs=N_STUBS, n_mons=1, racks=RACKS) as c:
            c.create_pool(POOL, size=3, pg_num=PG_NUM, min_size=2)
            planner = StormPlanner(cluster=c, seed=SEED, n_tenants=2,
                                   pool=POOL)
            planner.run(EVENTS)
            planner.quiesce()
            summary["metadata"] = planner.metadata()
            checker = StormInvariantChecker(c, planner)
            try:
                summary["invariants"] = checker.check()
            except AssertionError as e:
                problems.append(f"invariant violation: {e}")
            if not c.acked:
                problems.append("storm acked no writes — traffic never "
                                "reached min_size, nothing was checked")
            if not c.remap["events"]:
                problems.append("storm committed no map changes — no "
                                "remap churn was forecast")
    except Exception as e:  # noqa: BLE001 — a crash IS the finding
        problems.append(f"storm crashed: {type(e).__name__}: {e}")
    try:
        summary["remap_storm"] = run_remap_storm(
            n_osds=128, pg_num=2048, seed=SEED, rounds=3, sample=64)
    except AssertionError as e:
        problems.append(f"remap storm drift: {e}")
    except Exception as e:  # noqa: BLE001
        problems.append(f"remap storm crashed: {type(e).__name__}: {e}")
    summary["elapsed_s"] = round(time.monotonic() - t0, 1)
    summary["problems"] = problems
    print(json.dumps(summary, indent=2, default=str))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
