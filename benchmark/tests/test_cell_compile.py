"""The device programs of the codec and placement cells compile for a
described v5e at the cells' own sizes, with no chip: what the chip's
compiler would refuse (scoped VMEM, tiling, memory) shows here first.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_cell_compile.py

Sizes come from the cells' files: RS(8,4) over 64 x 1 MiB objects (an
[8, 8 MiB] apply for encode, the 8 x 8 decode apply and the 1..3-row
parity re-encodes for decode) and CRUSH over the 32,768-PG pool.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _codec_shapes():
    cfg = _load("configs", "ec_rs84_cauchy.json")
    mix = _load("mixes", "encode_1mib.json")
    k, m = cfg["k"], cfg["m"]
    L = mix["objects_per_call"] * mix["object_bytes"] // k
    dec = _load("mixes", "decode4_1mib.json")
    return cfg, L, [("encode", m)] + [("decode", k)] + [
        ("reencode", int(p)) for p in dec["decode_block"] if int(p)]


@pytest.mark.parametrize("which", range(5))
def test_codec_apply_compiles(one_chip, which):
    import jax.numpy as jnp

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.ops.pallas_gf import (
        _apply_grouped,
        _kron_matrices,
        _pick_group,
        _pick_layout,
    )

    cfg, L, shapes = _codec_shapes()
    if which >= len(shapes):
        pytest.skip("no such apply in the cells")
    kind, rows = shapes[which]
    codec = ErasureCodePluginRegistry.instance().factory(dict(cfg["profile"]))
    if kind == "encode":
        mat = codec.coding
    elif kind == "decode":
        mat = codec._jax_codec.decode_matrix(tuple(range(4, 12)))
    else:
        mat = codec.coding[:rows]
    mat = np.ascontiguousarray(mat, np.uint8)
    n = mat.shape[1]
    G = _pick_group(rows, n)
    tile = _pick_layout(rows, n, G)
    Lp = -(-L // (G * tile)) * G * tile
    Bk, Pk = _kron_matrices(mat.tobytes(), mat.shape, G)
    compiled = _apply_grouped.lower(
        _spec(Bk.shape, jnp.int8, one_chip),
        _spec(Pk.shape, jnp.bfloat16, one_chip),
        _spec((n, Lp), jnp.uint8, one_chip),
        rows=rows, n=n, G=G, tile=tile, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < 16 * 10**9


def test_pool_map_compiles(one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp

    from ceph_tpu.crush import CompiledCrushMap, build_hierarchical_map
    from ceph_tpu.crush.batched import ln_planes_pallas
    from ceph_tpu.crush.mapper import _build_rule_fn

    cfg = _load("configs", "crush_1024osd_straw2.json")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cm = CompiledCrushMap(build_hierarchical_map(cfg["hosts"],
                                                 cfg["osds_per_host"]))
    fn, _ = _build_rule_fn(cm, cfg["rule_id"], cfg["size"], None, "limb",
                           ln_planes_pallas)
    compiled = fn.lower(
        _spec((cfg["pg_num"],), jnp.int32, one_chip),
        _spec((cfg["hosts"] * cfg["osds_per_host"],), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
