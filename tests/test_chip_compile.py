"""Compile the main-path kernels for a described TPU v5e chip.

Interpret mode (tests/test_pallas.py, tests/test_crush_limb.py) checks the
kernels' bytes but cannot see what the chip's compiler refuses: scoped
VMEM, tiling, a program that does not fit the device.  Here the TPU
compiler that ships with libtpu compiles each kernel of the main path at
the widths and layouts the code picks, for a chip that is described and
not attached.  Nothing runs, so this says nothing about results or time;
chip_smoke.py runs the same kernels on the chip.  The kernels keep the
names the benchmark's per-layer readers find them by in a device trace
(the `KERNEL` pattern of benchmark/layer_metrics/gf_kernel_roofline.py
and straw2_ns_per_map.py): a rename would leave those metrics nothing to
read.

The topology is described only inside the module fixture: only one
process may load libtpu at a time, and a test worker that loads it at
import would leave the others unable to collect this file.
"""
import ast
import os
import re

import numpy as np
import pytest

CHIP_HBM_BYTES = 16 << 30  # TPU v5e
READERS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "layer_metrics")


def _kernel_pattern(reader: str) -> str:
    """The `KERNEL` name pattern of benchmark/layer_metrics/<reader>.py,
    read from the file (the reader imports the benchmark's modules)."""
    with open(os.path.join(READERS, reader + ".py")) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "KERNEL"
                for t in node.targets):
            return ast.literal_eval(node.value)
    raise LookupError(f"no KERNEL in {reader}.py")


def _assert_named_for(reader: str, hlo_text: str) -> None:
    """Some instruction of the compiled program matches the pattern the
    reader looks for among the device trace's ops."""
    rx = re.compile(_kernel_pattern(reader))
    assert any(rx.search(line) for line in hlo_text.splitlines()), (
        f"no instruction matches {reader}'s KERNEL {rx.pattern!r}")


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # the compiler logs under /tmp unless told not to
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
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


def _codec(profile):
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    return ErasureCodePluginRegistry.instance().factory(profile)


def _rs84_encode():
    return _codec({"plugin": "jax", "k": "8", "m": "4",
                   "technique": "cauchy_good"}).coding


def _rs84_decode():
    codec = _codec({"plugin": "jax", "k": "8", "m": "4",
                    "technique": "cauchy_good"})
    return codec._jax_codec.decode_matrix((0, 2, 4, 5, 6, 8, 10, 11))


def _rs21_encode():
    return _codec({"plugin": "jax", "k": "2", "m": "1",
                   "technique": "reed_sol_van"}).coding


def _shec632_decode():
    codec = _codec({"plugin": "shec", "k": "6", "m": "3", "c": "2"})
    plan = codec.minimum_to_decode({2}, set(range(9)) - {2})
    return codec._decode_matrix(frozenset({2}), tuple(sorted(plan)))


def _clay84_repair():
    codec = _codec({"plugin": "clay", "k": "8", "m": "4"})
    return codec.repair_matrix(0, tuple(range(1, 12)))


@pytest.mark.parametrize("matrix", [
    _rs84_encode, _rs84_decode, _rs21_encode, _shec632_decode,
    _clay84_repair,
], ids=lambda f: f.__name__.lstrip("_"))
def test_gf_kernel_compiles(one_chip, matrix):
    """The Pallas GF kernel at the layout _pick_layout picks for each
    main-path matrix, under the kernel's own scoped-VMEM limit."""
    import jax.numpy as jnp

    from ceph_tpu.ops.pallas_gf import (
        _apply_grouped,
        _kron_matrices,
        _pick_group,
        _pick_layout,
    )

    mat = np.ascontiguousarray(matrix(), np.uint8)
    rows, n = mat.shape
    G = _pick_group(rows, n)
    tile = _pick_layout(rows, n, G)
    Bk, Pk = _kron_matrices(mat.tobytes(), mat.shape, G)
    compiled = _apply_grouped.lower(
        _spec(Bk.shape, jnp.int8, one_chip),
        _spec(Pk.shape, jnp.bfloat16, one_chip),
        _spec((n, 2 * G * tile), jnp.uint8, one_chip),
        rows=rows, n=n, G=G, tile=tile, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_named_for("gf_kernel_roofline", compiled.as_text())


def test_decode_rows_compile(one_chip):
    """RS(8,4) decode at the decode cell's shape: the k survivors as
    eight 1-D rows of 8 MiB, read by the kernel straight from the rows,
    under a [4, 8] rebuild matrix, in one launch.  The rows take the
    bytes the [8, L] block takes (a 1-D uint8 layout padded on the chip
    would show here)."""
    import jax.numpy as jnp

    from ceph_tpu.ops.pallas_gf import (
        _apply_grouped,
        _kron_matrices,
        _pick_group,
        _pick_layout,
    )

    codec = _codec({"plugin": "jax", "k": "8", "m": "4",
                    "technique": "cauchy_good"})
    use = (0, 2, 4, 5, 6, 8, 10, 11)
    mat = codec._jax_codec._rebuild_entry(use, [1, 3, 7, 9])[0]
    rows, n = mat.shape
    assert (rows, n) == (4, 8)
    L = 8 << 20
    G = _pick_group(rows, n)
    tile = _pick_layout(rows, n, G)
    Bk, Pk = _kron_matrices(mat.tobytes(), mat.shape, G)

    def compile_for(x):
        return _apply_grouped.lower(
            _spec(Bk.shape, jnp.int8, one_chip),
            _spec(Pk.shape, jnp.bfloat16, one_chip), x,
            rows=rows, n=n, G=G, tile=tile, interpret=False,
        ).compile()

    compiled = compile_for(
        tuple(_spec((L,), jnp.uint8, one_chip) for _ in range(n)))
    assert compiled.as_text().count("tpu_custom_call") == 1
    _assert_named_for("gf_kernel_roofline", compiled.as_text())
    block = compile_for(_spec((n, L), jnp.uint8, one_chip))
    got = compiled.memory_analysis().argument_size_in_bytes
    want = block.memory_analysis().argument_size_in_bytes
    assert abs(got - want) <= 0.05 * want, (got, want)


@pytest.mark.parametrize("lost,layout", [(2, (1, 3, 32, 8192)),
                                         (6, (1, 6, 16, 8192))],
                         ids=["data", "global_parity"])
def test_shec_decode_rows_compile(one_chip, lost, layout):
    """SHEC(6,3,2) single-erasure decode at the SHEC cell's shape: the
    least read set (3 rows, or 6 for the global parity) as 1-D rows of
    12 x 4 MiB / 6 padded to 171 stripe units of 4 KiB, 8,404,992 B (not
    a power of two), under a [1, n] decode matrix, in one launch.  The
    rows are padded to the layout on the device: the arguments are the
    rows' own bytes and the two expanded matrices, nothing more."""
    import jax.numpy as jnp

    from ceph_tpu.ops.pallas_gf import (
        _apply_grouped,
        _kron_matrices,
        _pick_group,
        _pick_layout,
    )

    codec = _codec({"plugin": "shec", "k": "6", "m": "3", "c": "2"})
    plan = codec.minimum_to_decode({lost}, set(range(9)) - {lost})
    mat = codec._decode_matrix(frozenset({lost}), tuple(sorted(plan)))
    rows, n = mat.shape
    G = _pick_group(rows, n)
    tile = _pick_layout(rows, n, G)
    assert (rows, n, G, tile) == layout
    # a 4 MiB object is 171 stripes of 6 x 4 KiB: a 700,416 B chunk
    L = 12 * 171 * 4096
    Bk, Pk = _kron_matrices(mat.tobytes(), mat.shape, G)
    compiled = _apply_grouped.lower(
        _spec(Bk.shape, jnp.int8, one_chip),
        _spec(Pk.shape, jnp.bfloat16, one_chip),
        tuple(_spec((L,), jnp.uint8, one_chip) for _ in range(n)),
        rows=rows, n=n, G=G, tile=tile, interpret=False,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    _assert_named_for("gf_kernel_roofline", compiled.as_text())
    assert compiled.memory_analysis().argument_size_in_bytes == \
        Bk.nbytes + 2 * Pk.size + n * L


def test_clay_relayouts_compile(one_chip):
    """The Clay repair's device relayouts around the apply, at the Clay
    cell's size: 11 helpers x 64 stripes x 16 planes of 8 KiB in, the
    [64, 64 x 8 KiB] output back to stripe-major bytes, each one
    kernel."""
    import jax.numpy as jnp

    from ceph_tpu.ec.plugins.clay import _plane_major, _stripe_major

    stripes, nB, sub_len = 64, 16, 8192
    rows = tuple(_spec((stripes * nB, sub_len), jnp.uint8, one_chip)
                 for _ in range(11))
    compiled = _plane_major.lower(rows, stripes).compile()
    assert compiled.memory_analysis().output_size_in_bytes == \
        11 * nB * stripes * sub_len
    assert compiled.as_text().count("tpu_custom_call") == 1
    compiled = _stripe_major.lower(
        _spec((64, stripes * sub_len), jnp.uint8, one_chip), stripes).compile()
    assert compiled.memory_analysis().output_size_in_bytes == \
        64 * stripes * sub_len
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_straw2_scorer_compiles(one_chip):
    """The straw2 scorer at the mapper's default tile, at the S=128 width
    of the 1024-OSD map's root bucket."""
    import jax.numpy as jnp

    from ceph_tpu.ops.pallas_crush import DEFAULT_TILE, straw2_scores_pallas

    B = 2 * DEFAULT_TILE
    compiled = straw2_scores_pallas.lower(
        _spec((B,), jnp.int32, one_chip), _spec((B,), jnp.int32, one_chip),
        _spec((B, 128), jnp.int32, one_chip),
        tile=DEFAULT_TILE, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_named_for("straw2_ns_per_map", compiled.as_text())


def test_fused_write_flush_compiles(one_chip):
    """The batched write flush's program: RS(8,4), 64 stripes of 4 KiB
    (512 B chunks) packed on the host into one [8, 32 Ki] block and
    applied through the production Pallas dispatch (its own padding and
    layout) in one launch; fits one chip."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ops.pallas_gf import apply_matrix_pallas

    mat = np.ascontiguousarray(_rs84_encode(), np.uint8)
    compiled = jax.jit(lambda x: apply_matrix_pallas(mat, x)).lower(
        _spec((mat.shape[1], 64 * 512), jnp.uint8, one_chip),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < CHIP_HBM_BYTES


def test_limb_rule_fn_compiles(one_chip, monkeypatch):
    """crush_do_rule_batch's limb-engine rule program for the 1024-OSD
    map (128 hosts x 8), 3 replicas, with the Pallas scorer inside it."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.crush import CompiledCrushMap, build_hierarchical_map
    from ceph_tpu.crush.batched import ln_planes_pallas
    from ceph_tpu.crush.mapper import _build_rule_fn
    from ceph_tpu.ops.pallas_crush import DEFAULT_TILE

    # the scorer picks compiled-vs-interpreted from the backend name;
    # this host's backend is the CPU, the target is the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cm = CompiledCrushMap(build_hierarchical_map(128, 8))
    fn, _width = _build_rule_fn(cm, 0, 3, None, "limb", ln_planes_pallas)
    compiled = fn.lower(
        _spec((DEFAULT_TILE,), jnp.int32, one_chip),
        _spec((1024,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the scorer as the crush cell's trace shows it: inside the rule fn
    _assert_named_for("straw2_ns_per_map", compiled.as_text())
