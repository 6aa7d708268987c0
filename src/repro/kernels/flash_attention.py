"""Flash attention (GQA, causal, optional sliding window) as a Pallas TPU kernel.

Tiling: grid = (batch, q_heads, Sq/block_q, Sk/block_k); the kv-block axis is the
innermost (sequential) grid dim, so the output tile and the online-softmax
running stats live in VMEM scratch across kv steps (output revisiting).  GQA is
expressed in the kv BlockSpec index_map (kv head = q head // rep) — kv tiles are
never materialized per q-head.  block_q/block_k default to 128 (MXU-aligned);
with bf16 inputs the working set per step is
  q(128×D) + k(128×D) + v(128×D) + scores(128×128) fp32 + acc(128×D) fp32
≈ 0.3 MB for D=128 — far under the ~16 MB v5e VMEM budget, leaving room for
double-buffered pipelining.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

NEG_INF = -1e30


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                           scale: float, block_q: int, block_k: int,
                           seq_len: int, causal: bool, swa_window):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    # skip fully-masked tiles (causal: tile in the future; SWA: tile left of
    # the window) — the triangular/banded schedule that halves causal FLOPs
    needed = jnp.bool_(True)
    if causal:
        needed = needed & ((ki * block_k) <= (qi * block_q + block_q - 1))
    if swa_window:
        needed = needed & ((ki + 1) * block_k - 1 > qi * block_q - swa_window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok = ok & (k_pos <= q_pos)
        if swa_window:
            ok = ok & (k_pos > q_pos - swa_window)
        ok = ok & (k_pos < seq_len)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + p @ v
        m_scr[:] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:]
                       / jnp.maximum(l_scr[:], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, swa_window=None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool):
    """q: (B, Hq, S, D), k/v: (B, Hkv, S, D) -> (B, Hq, S, D).

    Hq must be a multiple of Hkv (GQA); the kv index_map routes each q head to
    its group's kv head.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    rep = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        flash_attention_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_len=s, causal=causal, swa_window=swa_window)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),   # running max m
            pltpu.VMEM((block_q,), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, d), jnp.float32), # accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
