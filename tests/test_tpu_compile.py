"""Compile the chip's programs for a described TPU v5e, with no chip attached.

Interpret mode (tests/test_kernels.py) runs a Pallas kernel body on any
backend, so it cannot see what Mosaic refuses: a block not tiled (8, 128),
a scalar kept in vector memory, an op the TPU cannot legalize, a kernel
that outgrows its scoped VMEM.  These tests hand the real compiler the
production shapes: every ``*_pallas`` kernel at tm=512, the dense fused
tick (with and without its register plane) at the serving tier's 1000
blocks x 16 groups, and the mesh tick on four described chips.  Nothing
runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import distributed as D
from repro.core.types import IslaParams
from repro.kernels import isla_moments as K

TM = K.DEFAULT_TM
PARAMS = IslaParams()
# The serving tier's 4-key stack at 1000 blocks x 16 groups (34 000 cells):
# plain, GROUP BY, GROUP BY + WHERE and WHERE keys, one shared GROUP BY pane
# and one shared predicate pane.
N_BLOCKS, QUOTA = 1000, 512
N_GROUPS = (1, 16, 16, 1)
N_CELLS = N_BLOCKS * sum(N_GROUPS)
DENSE_STATIC = dict(params=PARAMS, mode="calibrated", geometry=None,
                    n_groups_list=N_GROUPS, gid_slots=(-1, 0, 0, -1),
                    valid_slots=(-1, -1, 0, 0), key_affine=None,
                    bound_slots=None)
# The tick's one packed upload: the value pane, the shared predicate and
# GROUP BY panes and the quota column (a register plane adds two hash panes).
PANES = D.PaneLayout(N_BLOCKS, QUOTA, n_valid=1, n_gid=1)
SKETCH_PANES = PANES._replace(n_hash=2)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    rows, n = 2 * TM, 16
    f32, u32, u8 = jnp.float32, jnp.uint32, jnp.uint8
    return {
        "isla_moments_pallas": (
            lambda v, b: K.isla_moments_pallas(v, b, tm=TM),
            [((rows, K.LANE), f32), ((4,), f32)]),
        "isla_moments_pallas_strided": (
            lambda v, b: K.isla_moments_pallas(v, b, tm=TM, stride=2),
            [((4 * TM, K.LANE), f32), ((4,), f32)]),
        "isla_moments_batched_pallas": (
            lambda v, b, p: K.isla_moments_batched_pallas(v, b, tm=TM,
                                                          prior=p),
            [((n, rows, K.LANE), f32), ((4,), f32), ((n, 2, 4), f32)]),
        "isla_moments_batched_pallas_per_cell_bounds": (
            lambda v, b: K.isla_moments_batched_pallas(v, b, tm=TM),
            [((n, rows, K.LANE), f32), ((n, 4), f32)]),
        "isla_moments_grouped_pallas": (
            lambda v, b: K.isla_moments_grouped_pallas(v, b, tm=TM),
            [((4, 4, rows, K.LANE), f32), ((4,), f32)]),
        "pilot_stats_pallas": (
            lambda v: K.pilot_stats_pallas(v, tm=TM),
            [((rows, K.LANE), f32)]),
        "isla_sketch_pallas": (
            lambda h, l, v, p: K.isla_sketch_pallas(h, l, v, tm=TM,
                                                    prior=p),
            [((n, rows, K.LANE), u32)] * 3
            + [((n, K.REG_ROWS, K.LANE), u8)]),
        "isla_fused_pallas": (
            lambda v, b, p, s: K.isla_fused_pallas(v, b, p, s, PARAMS,
                                                   tm=TM),
            [((n, rows, K.LANE), f32), ((n, 4), f32), ((n, 2, 4), f32),
             ((n,), f32)]),
        "isla_fused_sketch_pallas": (
            lambda v, b, p, r, h, l, va, s: K.isla_fused_sketch_pallas(
                v, b, p, r, h, l, va, s, PARAMS, tm=TM),
            [((n, rows, K.LANE), f32), ((4,), f32), ((n, 2, 4), f32),
             ((n, K.REG_ROWS, K.LANE), u8)]
            + [((n, rows, K.LANE), u32)] * 3 + [((), f32)]),
    }


KERNEL_CASES = _kernel_cases()


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_pallas_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNEL_CASES[name]
    args = [_spec(s, one_chip, dt) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not a fallback


def _dense_args(spec, layout=PANES):
    c, b, k = N_CELLS, N_BLOCKS, len(N_GROUPS)
    return (spec((c, 4)), spec((c, 4)), spec((c, 3)), spec((k * b,)),
            spec((1, 4)), spec(layout.shape(4)), spec((c,)),
            spec((k * b,)), spec((c,)))


@pytest.mark.parametrize("has_sketch", [False, True])
def test_fused_tick_dense_compiles_at_deployment_shape(one_chip, has_sketch):
    """The served hot path at 1000 x 16, and its Phase 1 contraction
    keeps full fp32 precision (a default-precision f32 dot rounds its
    operands to bf16 on a TPU).  The register plane is optional state:
    with it the launch carries a uint8 plane and its scatter-max, and
    without it the program has neither."""
    spec = (lambda s, dt=jnp.float32: _spec(s, one_chip, dt))
    layout = SKETCH_PANES if has_sketch else PANES
    regs = spec((N_CELLS, 4096), jnp.uint8) if has_sketch else None
    text = D.fused_tick_dense.lower(
        *_dense_args(spec, layout), regs=regs, **DENSE_STATIC,
        pane_layout=layout).compile().as_text()
    contraction = [ln for ln in text.splitlines()
                   if 'op_name="jit(fused_tick_dense)/dot_general"' in ln
                   and ("convolution(" in ln or " dot(" in ln)]
    assert contraction
    assert all("operand_precision={highest,highest}" in ln
               for ln in contraction)
    assert ("u8[" in text) == has_sketch
    assert ("scatter-max" in text) == has_sketch


@pytest.mark.parametrize("has_sketch", [False, True])
def test_cold_store_compiles_at_deployment_shape(one_chip, has_sketch):
    """A cold key's store build, one launch: the zero state plus the
    anchor constants, at the serving tier's 1000 blocks x 16 groups."""
    consts = _spec((6,), one_chip)
    compiled = D.cold_store.lower(consts, 16 * N_BLOCKS, N_BLOCKS,
                                  has_sketch).compile()
    assert compiled.memory_analysis() is not None


def _mesh_dense_args(topo):
    """A 4-chip cell mesh and the dense mesh tick's sharded arguments."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("cells",))
    assert mesh.size == 4
    row = NamedSharding(mesh, P("cells", None))
    vec = NamedSharding(mesh, P("cells"))
    rep = NamedSharding(mesh, P(None, None))
    c, b, q, k = N_CELLS, N_BLOCKS, QUOTA, len(N_GROUPS)
    args = (_spec((c, 4), row), _spec((c, 4), row), _spec((c, 3), row),
            _spec((k * b,), vec), _spec((b, q), row), _spec((b, q), row),
            _spec((b,), vec), (_spec((b, q), vec, jnp.int32),),
            (_spec((b, q), row),), _spec((1, 4), rep), _spec((c,), vec),
            _spec((k * b,), vec), _spec((c,), vec))
    return mesh, row, args


def _mesh_static(mesh):
    return (mesh, PARAMS, "calibrated", None, N_GROUPS,
            DENSE_STATIC["gid_slots"], DENSE_STATIC["valid_slots"], None,
            None, 1, 1)


def test_mesh_tick_dense_compiles_on_four_chips_with_one_psum(topo):
    """The mesh route's dense tick over a 4-chip mesh: its only
    cross-device collective is the psum of the O(groups) stat rows."""
    mesh, _, args = _mesh_dense_args(topo)
    fn = D.mesh_tick_dense_fn(*_mesh_static(mesh))
    compiled = fn.lower(*args, None, None, None).compile()
    stat_rows = sum(N_GROUPS) * 9
    assert D.collective_footprint(compiled.as_text()) == [
        ("all-reduce", stat_rows)]


def test_mesh_tick_dense_sketch_folds_registers_in_int32(topo):
    """The register fold crosses chips as int32.  A uint8 max all-reduce
    is rewritten by the TPU compiler into a max over packed 32-bit words
    (four register rows per word), which is not a per-byte max."""
    mesh, row, a = _mesh_dense_args(topo)
    pane = (N_BLOCKS, QUOTA)
    args = a + (None, _spec((N_CELLS, 4096), row, jnp.uint8),
                (_spec(pane, row, jnp.uint32), _spec(pane, row, jnp.uint32)))
    fn = D.mesh_tick_dense_fn(*_mesh_static(mesh))
    text = fn.lower(*args).compile().as_text()
    reduces = [ln for ln in text.splitlines() if " all-reduce(" in ln]
    assert reduces and not any(t in ln.split("all-reduce(")[0]
                               for ln in reduces for t in ("u8[", "u32["))
    assert sorted(D.collective_footprint(text)) == sorted([
        ("all-reduce", sum(N_GROUPS) * 9),
        ("all-reduce", sum(N_GROUPS) * 4096)])
