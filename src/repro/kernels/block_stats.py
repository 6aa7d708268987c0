"""Block-statistics sampling kernel — the paper's Algorithm-1 line 7 as one fused
reduction.

DV-DVFS needs, per data block: non-pad token count, grep-pattern match count, and
a token-mass proxy (sum of ids).  Doing this in one pass keeps the sampling
overhead at the paper's <1 % contract: a single streamed read of the block shard,
VMEM-resident accumulators, no intermediate materialization.

Two entry points:

  * ``block_stats_batched_pallas``  whole dataset: (n_blocks, R, L) -> (n_blocks, 3)
        grid = (n_blocks, row_tiles): ONE dispatch for every block, with a
        per-block valid-row count for ragged block sizes (rows at or beyond
        a block's count are masked out of the stats).
  * ``block_stats_pallas``          one block:   (N, L) -> (3,), the batched
        kernel over a single block of N valid rows.

Layout for the TPU compiler (Mosaic): the per-block row counts ride in SMEM
as a scalar-prefetch argument; each stat accumulates as an (8, L) vector tile
(row tiles are folded 8 sublanes at a time, so nothing is reduced across
lanes or stored as a scalar in VMEM); the grep window shifts are lane
rotations of the whole tile.  The wrapper sums the (8, L) tiles at the end.
``nonpad`` and ``matches`` are counted exactly in int32 and rounded once to
float32 on output (the same rounding ``ref.block_stats_ref`` applies);
``mass`` accumulates in float32.

``interpret`` is explicit here; ``repro.kernels.ops`` resolves it per
backend (Mosaic on a TPU, the Pallas interpreter on the CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_stats_batched_kernel", "block_stats_batched_pallas",
           "block_stats_pallas"]

_SUBLANES = 8


def _fold(x, block_rows: int):
    """(block_rows, L) -> (8, L): sum of the tile's 8-row slabs."""
    acc = x[:_SUBLANES]
    for k in range(1, block_rows // _SUBLANES):
        acc = acc + x[k * _SUBLANES:(k + 1) * _SUBLANES]
    return acc


def block_stats_batched_kernel(len_ref, tok_ref, nonpad_ref, match_ref,
                               mass_ref, *, pattern: tuple, block_rows: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        nonpad_ref[...] = jnp.zeros_like(nonpad_ref)
        match_ref[...] = jnp.zeros_like(match_ref)
        mass_ref[...] = jnp.zeros_like(mass_ref)

    toks = tok_ref[0]                          # (block_rows, L) int32
    length = toks.shape[1]
    shape = (block_rows, length)
    rows = j * block_rows + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    valid = rows < len_ref[i]

    # a window starts at column c <= L - p; the pattern's k-th token is
    # compared against the tile rotated left by k lanes (col c holds c + k)
    hits = valid & (cols <= length - len(pattern))
    for k, pk in enumerate(pattern):
        shifted = toks if k == 0 else pltpu.roll(toks, (-k) % length, 1)
        hits = hits & (shifted == pk)

    nonpad = (valid & (toks != 0)).astype(jnp.int32)
    mass = jnp.where(valid, toks, 0).astype(jnp.float32)
    nonpad_ref[0] += _fold(nonpad, block_rows)
    match_ref[0] += _fold(hits.astype(jnp.int32), block_rows)
    mass_ref[0] += _fold(mass, block_rows)


def block_stats_batched_pallas(tokens, lengths=None,
                               pattern: tuple = (17, 23, 5), *,
                               block_rows: int = 128, interpret: bool):
    """tokens: (n_blocks, R, L) int32 -> (n_blocks, 3) float32 stats.

    One ``pallas_call`` over a (n_blocks, row_tiles) grid computes every
    block's [nonpad, matches, mass] in a single dispatch.  ``lengths``
    (n_blocks,) gives each block's real row count for ragged datasets packed
    into the common R (rows at or beyond a block's length are masked out);
    ``None`` means all R rows are real.  R need not divide the tile: rows
    are zero-padded up to it and masked.
    """
    n_blocks, r, length = tokens.shape
    if lengths is None:
        lengths = jnp.full((n_blocks,), r, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    block_rows = -(-min(block_rows, r) // _SUBLANES) * _SUBLANES
    pad = (-r) % block_rows
    if pad:
        tokens = jnp.pad(tokens, ((0, 0), (0, pad), (0, 0)))
    kernel = functools.partial(block_stats_batched_kernel,
                               pattern=tuple(pattern), block_rows=block_rows)
    acc_spec = pl.BlockSpec((1, _SUBLANES, length),
                            lambda i, j, lens: (i, 0, 0))
    acc_shape = (n_blocks, _SUBLANES, length)
    nonpad, matches, mass = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks, (r + pad) // block_rows),
            in_specs=[pl.BlockSpec((1, block_rows, length),
                                   lambda i, j, lens: (i, j, 0))],
            out_specs=[acc_spec, acc_spec, acc_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(acc_shape, jnp.int32),
                   jax.ShapeDtypeStruct(acc_shape, jnp.int32),
                   jax.ShapeDtypeStruct(acc_shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="block_stats",
    )(lengths, tokens)
    return jnp.stack([nonpad.sum(axis=(1, 2)).astype(jnp.float32),
                      matches.sum(axis=(1, 2)).astype(jnp.float32),
                      mass.sum(axis=(1, 2))], axis=1)


def block_stats_pallas(tokens, pattern: tuple = (17, 23, 5), *,
                       block_rows: int = 128, interpret: bool):
    """tokens: (N, L) int32 -> stats (3,) float32: [nonpad, matches, mass]."""
    return block_stats_batched_pallas(tokens[None], None, pattern,
                                      block_rows=block_rows,
                                      interpret=interpret)[0]
