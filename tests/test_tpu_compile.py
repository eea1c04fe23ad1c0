"""Compile the served path's device programs for a TPU v5e, without a chip.

The TPU compiler ships with jaxlib's TPU plug-in and compiles for a
*described* ``v5e:2x2`` topology: it refuses what the chip would refuse
(block tiling, unsupported casts, VMEM/SMEM overruns) at no chip time.
Interpret-mode tests cannot see any of that.  Covered here, at the
widths the §6.1 deployment runs:

  * the ragged fleet update for cs, cms, UnivMon level rows (8 and 16)
    and §4.4 mitigation, in every value mode ``resolve_value_mode`` can
    pick, at the geometry ``select_geometry`` picks;
  * the single-device query gather/merge (frequency and UnivMon);
  * the single-fragment kernel and the dense-rectangle oracle, which
    share the update body;
  * the 4-device ``shard_map`` merge on a ``switch`` mesh.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU library), and the persistent compilation
cache is off around the compiles: an entry compiled for a described chip
cannot be read back without one.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.fleet import CSR_BLK
from repro.kernels.sketch_update import fleet as FK
from repro.kernels.sketch_update.kernel import (LANE, pow2_width_cap,
                                                select_geometry)

#: One 8-epoch window of the §6.1 deployment: 20 switches; cs/cms
#: fragments at 128 KiB (32768 counters, the widest ~124K), UnivMon at
#: 512 KiB over 16 levels.
E, F = 8, 20
CS_WIDTH, WIDE_WIDTH, UM_WIDTH, NARROW_WIDTH = 32768, 123974, 8192, 768
N_BLOCKS = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    set_log_dir = "TPU_LOG_DIR" not in os.environ
    if set_log_dir:
        os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                      # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
        if set_log_dir:
            del os.environ["TPU_LOG_DIR"]


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def switch_mesh(topo):
    from repro.launch.mesh import make_switch_mesh

    return make_switch_mesh(devices=topo.devices[:4])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


#: name -> (width, n_sub, n_levels, mitigation)
UPDATE_CASES = {
    "cs": (CS_WIDTH, 2, 1, False),
    # FatTree(14) at 2 KiB/switch: narrower than one 1024-column block.
    "cs-narrow": (NARROW_WIDTH, 8, 1, False),
    "cms-wide": (WIDE_WIDTH, 16, 1, False),
    "cs-mitigation": (CS_WIDTH, 4, 1, True),
    "um-16-levels": (UM_WIDTH, 2, 16, False),
    "um-8-levels-mitigation": (UM_WIDTH // 2, 8, 8, True),
}


@pytest.mark.parametrize("mode", ["count", "limb", "f32"])
@pytest.mark.parametrize("case", sorted(UPDATE_CASES))
def test_ragged_update_compiles(one_chip, case, mode):
    width, n_sub, n_levels, mit = UPDATE_CASES[case]
    _, w_blk = select_geometry(width, n_sub, mode)
    w_blk = min(w_blk, pow2_width_cap(width))
    padded = width + (-width) % w_blk
    rows = E * F * n_levels
    tiles = N_BLOCKS * CSR_BLK // LANE
    args = (_sds((tiles, LANE), jnp.uint32, one_chip),
            _sds((tiles, LANE), jnp.float32, one_chip),
            _sds((tiles, LANE), jnp.uint32, one_chip),
            _sds((rows, FK.N_PARAMS), jnp.int32, one_chip),
            _sds((N_BLOCKS,), jnp.int32, one_chip))
    compiled = FK._fleet_update_ragged_jit.lower(
        *args, n_sub_max=n_sub, width_max=width, padded_width=padded,
        log2_te=16, signed=True, blk=CSR_BLK, w_blk=w_blk, value_mode=mode,
        n_levels=n_levels, with_mitigation=mit, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["count", "f32"])
def test_single_fragment_and_dense_kernels_compile(one_chip, mode):
    """The kernels that share ``block_contrib`` with the ragged update:
    the single-fragment kernel and the dense-rectangle oracle."""
    from repro.kernels.sketch_update.kernel import sketch_update_pallas

    blk, w_blk = select_geometry(CS_WIDTH, 4, mode)
    rows = 4 * blk // LANE
    single = jax.jit(sketch_update_pallas, static_argnames=(
        "hash_width", "padded_width", "n_sub", "log2_te", "col_seed",
        "sign_seed", "sub_seed", "signed", "blk", "w_blk", "value_mode",
        "interpret")).lower(
        _sds((rows, LANE), jnp.uint32, one_chip),
        _sds((rows, LANE), jnp.float32, one_chip),
        _sds((rows, LANE), jnp.uint32, one_chip), hash_width=CS_WIDTH,
        padded_width=CS_WIDTH, n_sub=4, log2_te=16, col_seed=1,
        sign_seed=2, sub_seed=3, signed=True, blk=blk, w_blk=w_blk,
        value_mode=mode, interpret=False).compile()
    dense = FK._fleet_update_jit.lower(
        _sds((F, rows, LANE), jnp.uint32, one_chip),
        _sds((F, rows, LANE), jnp.float32, one_chip),
        _sds((F, rows, LANE), jnp.uint32, one_chip),
        _sds((F, FK.N_PARAMS), jnp.int32, one_chip), n_sub_max=4,
        padded_width=CS_WIDTH, log2_te=16, signed=True, blk=blk,
        w_blk=w_blk, value_mode=mode, interpret=False).compile()
    for compiled in (single, dense):
        assert "tpu_custom_call" in compiled.as_text()


def _query_args(sharding, rows, n_sub, width):
    return (_sds((E, rows, n_sub, width), jnp.float32, sharding),
            _sds((E, rows), jnp.uint32, sharding),
            _sds((E, rows), jnp.uint32, sharding),
            _sds((E, rows), jnp.uint32, sharding),
            _sds((rows,), jnp.int32, sharding),
            _sds((rows,), jnp.int32, sharding))


@pytest.mark.parametrize("kind,mitigate", [("cs", True), ("cms", False)])
def test_query_merge_compiles(one_chip, kind, mitigate):
    from repro.kernels.sketch_query.engine import _gather_merge

    compiled = _gather_merge.lower(
        *_query_args(one_chip, F, 2, CS_WIDTH),
        _sds((F,), jnp.bool_, one_chip), _sds((F,), jnp.bool_, one_chip),
        _sds((1024,), jnp.uint32, one_chip),
        kind=kind, mitigate=mitigate).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("kind,mitigate", [("cs", False), ("cs", True),
                                           ("cms", False), ("um", False)])
def test_query_rows_merge_compiles(one_chip, kind, mitigate):
    """The batched multi-path merge at the §6.1 query's shape: one
    2^15-key chunk over 192 paths (bucket 256) of 5 on-path rows."""
    from repro.kernels.sketch_query.engine import (KEY_CHUNK,
                                                   _gather_merge_rows)

    rows = F * 16 if kind == "um" else F
    width = UM_WIDTH if kind == "um" else WIDE_WIDTH
    compiled = _gather_merge_rows.lower(
        _sds((E, rows, 2, width), jnp.float32, one_chip),
        _sds((rows, 3 * E + 4), jnp.uint32, one_chip),
        _sds((256, 5), jnp.int32, one_chip),
        _sds((256,), jnp.bool_, one_chip),
        _sds((KEY_CHUNK,), jnp.uint32, one_chip),
        _sds((KEY_CHUNK,), jnp.int32, one_chip),
        kind=kind, mitigate=mitigate).compile()
    assert compiled.memory_analysis() is not None


def test_um_query_merge_compiles(one_chip):
    from repro.kernels.sketch_query.engine import _gather_merge_um

    compiled = _gather_merge_um.lower(
        *_query_args(one_chip, F * 16, 2, UM_WIDTH),
        _sds((F,), jnp.bool_, one_chip),
        _sds((1024,), jnp.uint32, one_chip), n_levels=16).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_merge_compiles_on_4_chips(switch_mesh):
    """The ``shard_map`` merge over a 4-chip ``switch`` mesh: 245
    FatTree(14) fragments padded to 248 rows; only the gathered (E, R,
    K) estimates cross chips (an all-gather, never the counter shards)."""
    from repro.kernels.sketch_query.engine import (_sharded_gather_merge,
                                                   shard_padded_rows)

    n_rows = 245
    r_pad = shard_padded_rows(n_rows, 4)
    stack = NamedSharding(switch_mesh, P(None, "switch", None, None))
    row = NamedSharding(switch_mesh, P(None, "switch"))
    per_row = NamedSharding(switch_mesh, P("switch"))
    rep = NamedSharding(switch_mesh, P())
    fn = _sharded_gather_merge(switch_mesh, "cs", False, n_rows)
    compiled = fn.lower(
        _sds((E, r_pad, 16, 4096), jnp.float32, stack),
        _sds((E, r_pad), jnp.uint32, row), _sds((E, r_pad), jnp.uint32, row),
        _sds((E, r_pad), jnp.uint32, row),
        _sds((r_pad,), jnp.int32, per_row), _sds((r_pad,), jnp.int32, per_row),
        _sds((E, n_rows), jnp.bool_, rep), _sds((r_pad,), jnp.bool_, per_row),
        _sds((1024,), jnp.uint32, rep)).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    assert np.prod(compiled.input_shardings[0][0].mesh.devices.shape) == 4
