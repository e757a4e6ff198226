"""Device-mesh sharding of EC batches — the ICI/DCN story (SURVEY.md §5.8).

The reference scales by spreading PGs over OSDs with CRUSH and shipping
sub-ops over the AsyncMessenger (reference: src/msg/async/AsyncMessenger.cc);
the TPU-native equivalent parallelizes the *batch*: shard-length (stripe) and
CRUSH-x batches are laid out over a jax.sharding.Mesh so XLA rides ICI with
collectives only where the computation genuinely mixes shards:

- encode / matrix apply: contraction is over bitplanes (replicated), batch
  axis is shard length -> purely local compute, zero collectives (the DP/SP
  analog; SURVEY.md §2.9).
- distributed recovery: surviving shard rows live on different devices and
  the decode mixes all of them -> one all_gather over the shard axis (the
  TP analog of ECBackend reading k shards across OSDs, reference:
  src/osd/ECBackend.cc :: objects_read_and_reconstruct).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.device_policy import get_device_policy, mesh_over
from ..ops.bitplane import _apply_bitmatrix, _bitmatrix_for

# jax.shard_map graduated from jax.experimental at 0.4.x boundaries and
# renamed its replication-check kwarg (check_rep -> check_vma) on the
# way; accept either spelling so the decode path works on the pinned
# runtime
_shard_map = getattr(jax, "shard_map", None)
_CHECK_KW = "check_vma"
if _shard_map is None:  # pragma: no cover - depends on installed jax
    from jax.experimental.shard_map import shard_map as _shard_map

    _CHECK_KW = "check_rep"

LEN_AXIS = "shard_len"  # stripe-batch axis (data/sequence-parallel analog)
ROW_AXIS = "shard_row"  # shard-id axis (tensor-parallel analog)


def make_mesh(n_devices: int | None = None, axis: str = LEN_AXIS,
              policy=None) -> Mesh:
    """Mesh over the policy-granted devices (cephtopo: the ambient
    jax.devices() probe moved behind the injected DevicePolicy; the cpu
    variant yields a 1-device mesh, a sentinel-shrunk policy a smaller
    one).  ``policy=None`` consults the process-wide policy the first
    daemon configured; ``n_devices`` keeps the historical take-first-n
    cap so MULTICHIP_r05 callers are unchanged."""
    pol = policy if policy is not None else get_device_policy()
    return pol.mesh(n_devices, axis)


def sharded_apply_matrix(mesh: Mesh, mat: np.ndarray, chunks) -> jax.Array:
    """GF matrix apply with the shard-length axis split across the mesh.

    chunks [n, L] with L sharded; the bitmatrix is replicated; no
    collectives are inserted (verified by the multichip dryrun).
    """
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    B = _bitmatrix_for(mat)
    chunks = jnp.asarray(chunks, dtype=jnp.uint8)
    in_shard = NamedSharding(mesh, P(None, LEN_AXIS))
    rep = NamedSharding(mesh, P(None, None))
    chunks = jax.device_put(chunks, in_shard)
    B = jax.device_put(B, rep)
    fn = jax.jit(_apply_bitmatrix, out_shardings=in_shard)
    return fn(B, chunks)


def distributed_decode(mesh: Mesh, decode_mat: np.ndarray, shards) -> jax.Array:
    """Recover data when the k surviving shard rows are sharded over devices.

    shards [k, L] with the ROW axis sharded (each device holds some shard
    rows, like OSDs holding EC shards); the decode matrix mixes every row, so
    shard rows are all-gathered over ICI, then each device computes the full
    [k, L] reconstruction of its L-slice.  Uses shard_map + all_gather — the
    explicit-collective formulation of SURVEY.md §7 step 7.
    """
    k, L = shards.shape
    mat = np.ascontiguousarray(decode_mat, dtype=np.uint8)
    B = _bitmatrix_for(mat)
    shards = jnp.asarray(shards, dtype=jnp.uint8)
    row_mesh = mesh_over(mesh.devices, ROW_AXIS)
    n = row_mesh.devices.size
    if k % n != 0:
        # pad shard rows to a multiple of the mesh (zero rows are inert:
        # their bitmatrix columns are zero because decode_mat has k columns)
        pad = n - k % n
        shards = jnp.concatenate([shards, jnp.zeros((pad, L), jnp.uint8)])
        B = jnp.concatenate(
            [B, jnp.zeros((B.shape[0], pad * 8), jnp.int8)], axis=1
        )

    @partial(
        _shard_map,
        mesh=row_mesh,
        in_specs=(P(None, None), P(ROW_AXIS, None)),
        out_specs=P(None, None),
        # after the all_gather every device computes the same full result;
        # that replication isn't statically inferable, so skip the check
        # (check_vma on current jax, check_rep on the experimental home)
        **{_CHECK_KW: False},
    )
    def _decode(B_full, shard_slice):
        gathered = jax.lax.all_gather(
            shard_slice, ROW_AXIS, axis=0, tiled=True
        )
        return _apply_bitmatrix(B_full, gathered)

    return _decode(B, shards)
