"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves through ``default_interpret``: on a TPU the
kernels compile with Mosaic; on the CPU (the test suite, with
``JAX_PLATFORMS=cpu``) the kernel bodies run in the Pallas interpreter;
any other backend is an error rather than a silent fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.block_stats import (block_stats_batched_pallas,
                                       block_stats_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

__all__ = ["flash_attention", "ssd_scan", "block_stats",
           "block_stats_batched", "default_interpret"]


def default_interpret() -> bool:
    """Pallas execution mode for the default backend: False (Mosaic) on a
    TPU, True (interpreter) on the CPU; raises on anything else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas execution mode for backend {backend!r}: "
                       "kernels compile on 'tpu' and interpret on 'cpu'")


@functools.partial(jax.jit, static_argnames=("causal", "swa_window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, swa_window=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return flash_attention_pallas(q, k, v, causal=causal,
                                  swa_window=swa_window, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, b_mat, c_mat, *, chunk: int = 128,
             interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return ssd_scan_pallas(x, dt, a_log, b_mat, c_mat, chunk=chunk,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("pattern", "block_rows",
                                             "interpret"))
def block_stats(tokens, pattern: tuple = (17, 23, 5), *, block_rows: int = 128,
                interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return block_stats_pallas(tokens, pattern, block_rows=block_rows,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("pattern", "block_rows",
                                             "interpret"))
def block_stats_batched(tokens, lengths=None, pattern: tuple = (17, 23, 5), *,
                        block_rows: int = 128, interpret: bool | None = None):
    """Whole-dataset stats: (n_blocks, R, L) [+ (n_blocks,) lengths] -> (n_blocks, 3)."""
    interpret = default_interpret() if interpret is None else interpret
    return block_stats_batched_pallas(tokens, lengths, pattern,
                                      block_rows=block_rows,
                                      interpret=interpret)
