"""cephtopo CI smoke: one encode path, three device topologies
(qa/ci_gate.sh step 11).

The DevicePolicy refactor's whole claim is that topology is a *value*:
the same production encode (`parallel.sharded_apply_matrix` through
`make_mesh(policy=...)`) must produce bit-identical output whether the
policy grants

1. ``cpu``  — the 1-device CPU-fallback mesh (the laptop-test shape);
2. ``mesh`` — every device of the virtual 8-way host mesh (the
   multi-chip shape, conftest-style);
3. ``mesh`` with two devices pinned failed — the sentinel-degraded
   shape: the mesh SHRINKS to the 6 survivors instead of wedging.

Every device/mesh decision in this smoke routes through DevicePolicy —
the smoke is itself CL9-clean, which is the point.

Exit 0 on success; 1 with a `problems` list otherwise.  Prints one JSON
summary on stdout (the gate archives it next to the SARIF artifacts).
"""
from __future__ import annotations

import json
import os
import sys

from .smoke_util import wait_for as _wait

N_VIRTUAL = 8      # virtual host devices (matches tests/conftest.py)
PINNED_BAD = 2     # devices the degraded policy pins failed
K, M = 8, 4        # EC geometry
L = 3840           # stripe length: divisible by 1, 6, and 8


def main() -> int:
    # the virtual multi-device mesh must be requested before the first
    # backend init; importing ceph_tpu.qa already imported jax (which
    # read JAX_PLATFORMS then), so the cpu pin goes through the config
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={N_VIRTUAL}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from ..common.device_policy import DevicePolicy, reset_device_policy
    from ..gf import cauchy_good_coding_matrix
    from ..gf.reference_codec import encode_chunks
    from ..parallel import make_mesh, sharded_apply_matrix

    problems: list[str] = []
    summary: dict = {"smoke": "topology", "n_virtual": N_VIRTUAL}

    # a stray policy from an earlier in-process daemon must not leak in
    reset_device_policy()

    full = DevicePolicy("mesh")
    if not _wait(lambda: full.mesh_size() >= N_VIRTUAL, timeout=10):
        problems.append(
            f"virtual mesh never reached {N_VIRTUAL} devices "
            f"(got {full.mesh_size()}; XLA_FLAGS not honored?)")
        summary["problems"] = problems
        print(json.dumps(summary, indent=2, default=str))
        return 1

    # pin the LAST two granted rows failed — deterministic stand-in for
    # two sentinel probe failures (same "platform:id" row format)
    bad = tuple(f"{d.platform}:{d.id}" for d in full.devices()[-PINNED_BAD:])
    policies = {
        "cpu-1": DevicePolicy("cpu"),
        f"mesh-{N_VIRTUAL}": DevicePolicy("mesh"),
        "degraded": DevicePolicy("mesh", failed=bad),
    }
    summary["pinned_failed"] = list(bad)

    want_sizes = {
        "cpu-1": 1,
        f"mesh-{N_VIRTUAL}": N_VIRTUAL,
        "degraded": N_VIRTUAL - PINNED_BAD,
    }

    coding = cauchy_good_coding_matrix(K, M)
    data = np.random.default_rng(16).integers(
        0, 256, (K, L), dtype=np.uint8)
    reference = encode_chunks(coding, data)

    sizes: dict[str, int] = {}
    for name, pol in policies.items():
        mesh = make_mesh(policy=pol)
        sizes[name] = int(mesh.devices.size)
        if sizes[name] != want_sizes[name]:
            problems.append(
                f"{name}: mesh has {sizes[name]} devices, "
                f"want {want_sizes[name]}")
            continue
        got = np.asarray(sharded_apply_matrix(mesh, coding, data))
        if not np.array_equal(got, reference):
            problems.append(
                f"{name}: encode output diverged from the reference "
                f"({int((got != reference).sum())} of {got.size} bytes)")
    summary["mesh_sizes"] = sizes

    # the degraded mesh must actually exclude the pinned rows
    deg_rows = {f"{d.platform}:{d.id}"
                for d in policies["degraded"].devices()}
    if deg_rows & set(bad):
        problems.append(
            f"degraded policy still grants pinned-failed devices: "
            f"{sorted(deg_rows & set(bad))}")

    if not problems:
        summary["parity"] = "bit-identical across all topologies"
    summary["problems"] = problems
    print(json.dumps(summary, indent=2, default=str))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
