"""Compile every update-kernel shape the replay cells launch at seed 1,
for a described TPU v5e, without a chip.

The shapes come from the reference's subepoch trajectory: per 8-epoch
window, one launch per n_sub group (the grouped dispatch of
``repro.core.fleet``), with its rows, its width ceiling and its bucketed
packet-block count.  What the chip's compiler would refuse is refused
here at no chip time.
"""
import os

import numpy as np
import pytest

from harness import deploy, find


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(desc.devices[0])


def _launches(name):
    """(rows, blocks, n_sub, width) of every grouped launch of a pass."""
    from repro.core.fleet import CSR_BLK, _bucket_blocks

    spec = find.cell_spec(name)
    b = deploy.build(spec["config"], 1)
    res = b.kind.replay(b.ref)
    tr = b.trace
    hops = tr.path_mat[tr.pkt_flow]
    pkt, pos = np.nonzero(hops >= 0)
    n_sw = len(b.widths)
    obs = np.bincount((tr.pkt_ts[pkt] >> tr.log2_te) * n_sw + hops[pkt, pos],
                      minlength=tr.n_epochs * n_sw).reshape(tr.n_epochs, n_sw)
    out = set()
    for e0 in range(0, tr.n_epochs, b.window):
        n = res.n_used[e0]
        for g in np.unique(n):
            sel = np.flatnonzero(n == g)
            lens = obs[e0:e0 + b.window, sel].ravel()
            nb = _bucket_blocks(int(np.maximum(1, -(-lens // CSR_BLK)).sum()))
            out.add((len(lens), nb, int(g), int(b.widths[sel].max())))
    return b, sorted(out)


@pytest.mark.parametrize("name", ["ft4-cs.replay", "ft14-cms.replay"])
def test_cell_kernels_compile(one_chip, name):
    import jax
    import jax.numpy as jnp
    from repro.core.fleet import CSR_BLK
    from repro.kernels.sketch_update import fleet as FK
    from repro.kernels.sketch_update.kernel import (LANE, pow2_width_cap,
                                                    select_geometry)

    b, launches = _launches(name)
    sk = b.cfg["sketch"]
    assert launches
    for rows, nb, n_sub, width in launches:
        w_blk = min(select_geometry(width, n_sub, "count")[1],
                    pow2_width_cap(width))
        pad_w = (-width) % w_blk

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        stream = (nb * CSR_BLK // LANE, LANE)
        hlo = FK._fleet_update_ragged_jit.lower(
            sds(stream, jnp.uint32), sds(stream, jnp.float32),
            sds(stream, jnp.uint32), sds((rows, FK.N_PARAMS), jnp.int32),
            sds((nb,), jnp.int32), n_sub_max=n_sub, width_max=width,
            padded_width=width + pad_w, log2_te=b.trace.log2_te,
            signed=sk["kind"] == "cs", blk=CSR_BLK, w_blk=w_blk,
            value_mode="count", n_levels=1,
            with_mitigation=bool(sk["mitigation"]), interpret=False)
        assert "tpu_custom_call" in hlo.as_text()
        hlo.compile()
