"""Compile rehearsals for the census hot path on a described TPU v5e.

Nothing here runs on a chip: the TPU compiler builds the kernels for a
v5e that is described, not attached, and refuses what the chip would
refuse (misaligned blocks, unsupported lowerings, VMEM overruns,
programs larger than device memory).  Each compile takes a second or two.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.graph import GraphArrays
from repro.engine import EngineConfig
from repro.engine.backends import make_pallas_chunk_fn
from repro.engine.ops import OpLayout, resolve_ops
from repro.engine.plan import GraphMeta
from repro.kernels.ops import gather_blocks_per_row, gather_tiles_device
from repro.kernels.triad_census import census_tiles_pallas

#: full-size eatSR (``paper_profile("eatSR", scale_down=1)``) plan shapes
EATSR_META = GraphMeta(n_bucket=32768, k=8192, member_iters=15,
                       m_out_bucket=524288, m_nbr_bucket=1048576)
EATSR_CHUNK = 8192
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("K", [32, 128, 512, 4096, 8192])
def test_census_kernel_compiles_for_v5e(one_chip, K):
    D = 256
    vec = _spec((D,), jnp.int32, one_chip)
    flag = _spec((D,), jnp.bool_, one_chip)
    tile = _spec((D, K), jnp.int32, one_chip)
    n = _spec((), jnp.int32, one_chip)
    fn = jax.jit(lambda u, v, n, us, s, l, sl, ll: census_tiles_pallas(
        u, v, n, us, s, l, sl, ll, block=32, interpret=False, reduce=False))
    compiled = fn.lower(vec, vec, n, flag, tile, tile, vec, vec).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _arrays(m, sharding):
    i32 = jnp.int32
    return GraphArrays(
        out_ptr=_spec((m.n_bucket + 1,), i32, sharding),
        out_idx=_spec((m.m_out_bucket,), i32, sharding),
        nbr_ptr=_spec((m.n_bucket + 1,), i32, sharding),
        nbr_idx=_spec((m.m_nbr_bucket,), i32, sharding),
        nbr_deg=_spec((m.n_bucket,), i32, sharding),
        nbr_code=_spec((m.m_nbr_bucket,), i32, sharding))


def _gather_index_counts(hlo: str) -> list:
    """Indices of every gather in compiled HLO text: the result's
    elements outside its offset (slice) dimensions."""
    counts = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* gather\(.*?"
                         r"offset_dims=\{([\d,]*)\}", hlo):
        dims = [int(d) for d in m.group(1).split(",") if d]
        offset = {int(d) for d in m.group(2).split(",") if d}
        counts.append(math.prod(d for i, d in enumerate(dims)
                                if i not in offset))
    return counts


@pytest.mark.parametrize("K", [32, 128, 512, 4096, 8192])
def test_tile_gather_fetches_blocks_for_v5e(one_chip, K):
    """The two direction-coded tiles of a chunk of 8192 dyads compile to
    block gathers with no loop: no gather takes more indices than one
    per aligned block of every tile row."""
    chunk = 8192
    rows = _spec((chunk,), jnp.int32, one_chip)
    valid = _spec((chunk,), jnp.bool_, one_chip)
    hlo = gather_tiles_device.lower(_arrays(EATSR_META, one_chip), rows,
                                    rows, valid, K=K).compile().as_text()
    assert " while(" not in hlo
    counts = _gather_index_counts(hlo)
    assert counts and max(counts) <= chunk * gather_blocks_per_row(K), counts


def test_fused_pallas_chunk_unit_fits_v5e(one_chip):
    """The engine's fused chunk unit (slice, tile gather, census kernel,
    hi/lo fold) at eatSR's top bucket: K = 8192, chunk = 8192."""
    config = EngineConfig(backend="pallas")
    layout = OpLayout(resolve_ops(("triad_census",)), EATSR_META, config)
    fn = make_pallas_chunk_fn(layout, config, {"traces": 0})
    m = EATSR_META
    i32 = jnp.int32
    arrays = _arrays(m, one_chip)
    scalar = _spec((), i32, one_chip)
    dyads = _spec((m.m_nbr_bucket // 2,), i32, one_chip)
    acc = _spec((layout.total_bins,), i32, one_chip)
    compiled = fn.lower(arrays, scalar, dyads, dyads, scalar, scalar, acc,
                        acc, K=m.k, chunk=EATSR_CHUNK, block=32,
                        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
