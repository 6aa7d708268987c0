"""Mamba-2 SSD chunk scan as a Pallas TPU kernel.

Grid = (batch·heads, n_chunks); the chunk axis is innermost/sequential, so the
carried state (P, N) lives in VMEM scratch across chunk steps — the classic
"grid-carried recurrence" pattern.  Per step the kernel does the three SSD
einsums for one (head, chunk) tile:

    intra:  (C·Bᵀ ⊙ L) · (dt ⊙ X)          — (q,q)·(q,P) matmuls on the MXU
    inter:  exp(seg) ⊙ (C · h_prev)
    state:  h = exp(seg_q)·h_prev + (tail·dt·B)ᵀ · X

Working set per step (q=chunk len, P=head dim, N=state): q·(P+2N+2) inputs +
q² decay + (P,N) state ≈ 0.5 MB fp32 at q=128, P=64, N=128 — VMEM-friendly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_kernel", "ssd_scan_pallas"]


def ssd_scan_kernel(x_ref, dt_ref, dta_ref, dta_row_ref, b_ref, c_ref, y_ref,
                    h_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (q, 1)
    dta = dta_ref[0].astype(jnp.float32)      # (q, 1)
    dta_row = dta_row_ref[0].astype(jnp.float32)  # (1, q)
    b = b_ref[0].astype(jnp.float32)          # (q, N)
    c = c_ref[0].astype(jnp.float32)          # (q, N)

    # inclusive prefix sums of dt·A as masked (q, q) reductions, in both
    # layouts: seg[i] = Σ_{k<=i} dta[k] down the column, and across the row
    iot = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jot = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = iot >= jot
    seg = jnp.sum(jnp.where(lower, dta_row, 0.0), axis=1,
                  keepdims=True)                                   # (q, 1)
    seg_row = jnp.sum(jnp.where(iot <= jot, dta, 0.0), axis=0,
                      keepdims=True)                               # (1, q)
    seg_last = jnp.sum(dta, axis=0, keepdims=True)                 # (1, 1)
    decay = jnp.where(lower, jnp.exp(seg - seg_row), 0.0)          # (q, q)

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))   # (q, q)
    xw = x * dt                               # dt ⊙ X  (q, P)
    y_intra = (scores * decay) @ xw
    y_inter = jnp.exp(seg) * jax.lax.dot_general(
        c, h_scr[:], (((1,), (1,)), ((), ())))                     # (q, P)

    tail = jnp.exp(seg_last - seg)            # (q, 1)
    state_upd = jax.lax.dot_general(
        x, b * (tail * dt), (((0,), (0,)), ((), ())))              # (P, N)
    h_scr[:] = h_scr[:] * jnp.exp(seg_last) + state_upd

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)


def ssd_scan_pallas(x, dt, a_log, b_mat, c_mat, *, chunk: int = 128,
                    interpret: bool):
    """x: (BH, S, P), dt: (BH, S), b/c: (BH, S, N) -> (y (BH, S, P), h (BH,P,N)).

    Wrapper flattens (batch, heads) and repeats grouped B/C outside (ops.py).
    """
    bh, s, p = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    a = -jnp.exp(a_log)                       # (BH,) negative
    dta = dt * a[:, None]

    kernel = functools.partial(ssd_scan_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bi, ci: (bi, 0, ci)),
            pl.BlockSpec((1, chunk, n), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda bi, ci: (bi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt[..., None], dta[..., None], dta[:, None, :], b_mat, c_mat)
    return y
