"""cephtopo — the ONE module where device topology is ambient.

The ROADMAP's multi-chip sharded data plane needs the same OSD code to
serve a laptop test (1 CPU device), an 8-chip mesh, and a
sentinel-shrunk degraded mesh.  That is impossible while `jax.devices()`
/ `jax.sharding.Mesh(...)` / `jax.default_backend()` probes are
scattered through the package: each ambient site hard-codes "whatever
this process happens to see" as the topology.  So topology becomes a
value: a ``DevicePolicy`` built ONCE from the daemon's conf
(``device_topology`` / ``device_mesh_shape``) and constructor-injected
into the seams that need it — the OSD daemon, bitplane/pipeline
dispatch, ``crush_do_rule_batch``, and ``parallel.mesh``.  cephlint CL9 (qa/analyzer/cl9_topology.py) enforces
the discipline: this file is the one allowlisted module where the
ambient probes may live; everywhere else they are lint errors.

Variants (the ``device_topology`` option):

- ``single`` — one chip: the default device only, mesh size 1.
- ``mesh``   — multi-chip: every healthy device (``device_mesh_shape``
  caps the axis length; 0 = all).
- ``cpu``    — CPU fallback: a 1-device mesh on the cpu platform, and
  ``backend()`` reports ``cpu`` so dispatch (pallas/limb engine) takes
  the host-safe path even when an accelerator exists.
- ``auto``   — ``mesh`` when more than one healthy device is visible,
  else ``single`` (the pre-policy behavior, preserved).

Sentinel-aware: the PR-15 per-device probe rows
(``ceph_backend_device_*``; kernel_telemetry.BackendSentinel.devices())
mark individual sick chips, and ``healthy_devices()`` subtracts them —
a failed probe SHRINKS the mesh instead of wedging
the data plane on a dead chip.  ``failed=`` pins additional devices out
(tests and the degraded-topology smoke inject deterministic failures
without running a sentinel cycle).
"""
from __future__ import annotations

import threading

TOPOLOGIES = ("auto", "single", "mesh", "cpu")


class DevicePolicy:
    """Resolved device-topology policy (see module docstring).

    Cheap value object: every accessor re-resolves against the live
    runtime + sentinel state, so a probe failure between two calls is
    reflected immediately (the mesh a caller already built keeps its
    devices — shrink applies to NEW grants, like OSDMap epochs).
    """

    def __init__(self, topology: str = "auto", mesh_shape: int = 0,
                 failed: tuple[str, ...] | frozenset[str] = ()):
        if topology not in TOPOLOGIES:
            raise ValueError(
                f"device_topology={topology!r}: want one of {TOPOLOGIES}")
        self.topology = topology
        self.mesh_shape = int(mesh_shape)
        #: "platform:id" rows pinned failed regardless of the sentinel
        self._failed = frozenset(failed)

    @classmethod
    def from_conf(cls, conf) -> "DevicePolicy":
        """The two declared knobs, read ONCE at daemon start."""
        return cls(topology=str(conf.get("device_topology")),
                   mesh_shape=int(conf.get("device_mesh_shape")))

    def __repr__(self) -> str:
        return (f"DevicePolicy(topology={self.topology!r}, "
                f"mesh_shape={self.mesh_shape}, "
                f"failed={sorted(self._failed)})")

    # -- the ambient probes: allowed HERE only (CL9 policy allowlist) ------
    def all_devices(self) -> list:
        """The raw runtime device list this variant draws from."""
        import jax

        if self.topology == "cpu":
            # true CPU fallback: prefer the host platform's devices even
            # on an accelerator box; some runtimes expose no cpu client,
            # so fall back to the default list (backend() still reports
            # cpu, which is what dispatch keys on)
            try:
                return list(jax.devices("cpu"))
            except RuntimeError:
                return list(jax.devices())
        return list(jax.devices())

    def backend(self) -> str:
        """The backend name dispatch decisions key on (`_want_pallas`,
        the CRUSH limb/i64 engine pick).  The cpu variant
        pins it to "cpu" — that is the fallback's whole point."""
        import jax

        if self.topology == "cpu":
            return "cpu"
        return jax.default_backend()

    # -- health ------------------------------------------------------------
    def _sentinel_failed(self) -> set[str]:
        """Device rows the backend sentinel's last probe cycle marked
        sick ("platform:id").  Lazy import: kernel_telemetry's probes
        resolve their platform through THIS module."""
        try:
            from .kernel_telemetry import SENTINEL

            rows = SENTINEL.devices()
        except Exception:
            return set()
        return {r.get("device") for r in rows or ()
                if not r.get("ok", True)}

    def healthy_devices(self) -> list:
        """all_devices() minus sentinel-failed and pinned-failed rows.
        Never empty: with EVERY device marked sick the policy keeps
        device 0 — the sentinel's is_degraded latch already reroutes the
        data plane, and a zero-device mesh would just move the wedge."""
        bad = self._failed | self._sentinel_failed()
        devs = self.all_devices()
        keep = [d for d in devs if f"{d.platform}:{d.id}" not in bad]
        return keep or devs[:1]

    # -- grants ------------------------------------------------------------
    def _grant(self, devs: list) -> list:
        """Apply the variant + mesh_shape cap to a candidate list."""
        if not devs:
            return devs
        if self.topology in ("single", "cpu"):
            return devs[:1]
        if self.topology == "auto" and len(devs) == 1:
            return devs[:1]
        if self.mesh_shape > 0:
            return devs[: self.mesh_shape]
        return devs

    def devices(self) -> list:
        """The devices this policy grants: healthy, variant-filtered."""
        return self._grant(self.healthy_devices())

    def default_device(self):
        return self.devices()[0]

    def mesh_size(self) -> int:
        return len(self.devices())

    def platform(self) -> str:
        """Platform of the first granted device (the telemetry probe's
        answer; touching the device list is deliberate — a wedged
        runtime must hang the sentinel's disposable worker here)."""
        return self.default_device().platform

    def mesh(self, n_devices: int | None = None, axis: str = "shard_len"):
        """A jax.sharding.Mesh over the granted devices.  ``n_devices``
        keeps parallel.mesh.make_mesh's historical cap semantics (take
        the first n); the cpu variant always yields a 1-device mesh."""
        devs = self.devices()
        if n_devices is not None:
            devs = devs[:n_devices]
        return mesh_over(devs, axis)


def mesh_over(devices, axis: str):
    """Build a 1-axis Mesh over an explicit device list/array.  The
    ``Mesh`` constructor lives here so every construction site in the
    package is inside the policy module (CL9 ambient-mesh); callers that
    re-axis an existing mesh (parallel.mesh.distributed_decode) route
    through this instead of constructing ambiently."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices).reshape(-1), (axis,))


# -- process-wide injection (first daemon wins, like the sentinel) ---------
_LOCK = threading.Lock()
_POLICY: DevicePolicy | None = None
_conf_applied = False


def configure_device_policy(policy: DevicePolicy) -> DevicePolicy:
    """Install the daemon's policy process-wide.  FIRST daemon in the
    process wins (kernel dispatch is process-wide, so a
    second daemon must not silently re-topologize them); returns the
    policy actually in force so the caller can hold the real one."""
    global _POLICY, _conf_applied
    with _LOCK:
        if not _conf_applied:
            _conf_applied = True
            _POLICY = policy
        return _POLICY


def get_device_policy() -> DevicePolicy:
    """The process-wide policy; before any daemon configures one, a
    default ``auto`` policy (the historical ambient behavior)."""
    global _POLICY
    with _LOCK:
        if _POLICY is None:
            _POLICY = DevicePolicy()
        return _POLICY


def reset_device_policy() -> None:
    """Drop the process-wide policy (tests / smoke harnesses only)."""
    global _POLICY, _conf_applied
    with _LOCK:
        _POLICY = None
        _conf_applied = False
