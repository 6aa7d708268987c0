"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

No chip is attached: the v5e topology is described, and each kernel is
lowered and compiled for one of its devices with ``interpret=False``, which
is where the TPU compiler refuses misaligned blocks, scalar stores to VMEM
or unsupported ops that interpret mode accepts.  The topology is described
inside a module fixture (never at import), since only one process at a time
may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.data import BlockDataset
from repro.kernels.block_stats import (block_stats_batched_pallas,
                                       block_stats_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

# HDFS-default 128 MiB block: 131,072 records x 256 int32 tokens
RECORDS, MAX_LEN = 131072, 256
SAMPLED = 6554      # ceil(0.05 * RECORDS): the streamed estimate's rows


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", [SAMPLED, RECORDS],
                         ids=["sampled", "full_block"])
def test_block_stats_batched_compiles(one_chip, rows):
    pattern = BlockDataset().grep_pattern
    text = _compiled_text(
        lambda t, n: block_stats_batched_pallas(t, n, pattern,
                                                interpret=False),
        one_chip, ((8, rows, MAX_LEN), jnp.int32), ((8,), jnp.int32))
    assert "tpu_custom_call" in text


def test_block_stats_single_ragged_compiles(one_chip):
    text = _compiled_text(
        lambda t: block_stats_pallas(t, interpret=False),
        one_chip, ((1000, MAX_LEN), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    shape = ((4, 16, 2048, 128), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
        one_chip, shape, shape, shape)
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    ssm = get_arch("mamba2-1.3b").ssm
    bh, s, p, n = ssm.n_heads, 2048, ssm.head_dim, ssm.d_state
    text = _compiled_text(
        lambda x, dt, a, b, c: ssd_scan_pallas(x, dt, a, b, c,
                                               chunk=ssm.chunk,
                                               interpret=False),
        one_chip, ((bh, s, p), jnp.float32), ((bh, s), jnp.float32),
        ((bh,), jnp.float32), ((bh, s, n), jnp.float32),
        ((bh, s, n), jnp.float32))
    assert "tpu_custom_call" in text
