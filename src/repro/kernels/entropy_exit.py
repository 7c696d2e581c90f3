"""Fused streaming softmax-entropy + exit gate (Pallas, TPU target).

Alg. 3 phases 1-2 over the exit-head logits: H = -sum p log p and the
decision H < tau, WITHOUT materializing the (B, V) softmax in HBM.  For the
256k-vocab assigned archs this matters: logits row = 256000 x 4B = 1 MB; the
fused kernel streams vocab blocks through VMEM keeping three running scalars
per row:
    m = running max,  S = sum e^{x-m},  U = sum e^{x-m} * x
    H = m + log S - U/S        (since H = log Z - E[x])
Rescaling on a new max multiplies S and U by e^{m_old - m_new}.
Grid = (row blocks, vocab blocks); vocab axis sequential with VMEM scratch.
The per-row state and outputs are ``(rows, 1)`` columns: Mosaic lowers no
rank-1 ``(block_rows,)`` block narrower than 128 lanes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _entropy_kernel(tau_ref, x_ref, h_ref, exit_ref, m_scr, s_scr, u_scr, *,
                    vocab: int, block_v: int):
    iv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)
        u_scr[...] = jnp.zeros_like(u_scr)

    x = x_ref[...].astype(jnp.float32)                       # (Br, Bv)
    col = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < vocab, x, NEG_INF)

    m_prev = m_scr[...]                                      # (Br, 1)
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(x - m_new)
    # padded lanes have p == exp(NEG_INF - m) == 0, so they contribute nothing
    s_scr[...] = s_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    u_scr[...] = u_scr[...] * alpha + jnp.sum(p * x, axis=1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(iv == nv - 1)
    def _finalize():
        S = jnp.maximum(s_scr[...], 1e-30)
        H = m_scr[...] + jnp.log(S) - u_scr[...] / S
        h_ref[...] = H
        exit_ref[...] = (H < tau_ref[0, 0]).astype(jnp.int32)


def entropy_exit_pallas(logits: jnp.ndarray, tau: jnp.ndarray, *,
                        block_rows: int = 8, block_v: int = 2048,
                        vocab: Optional[int] = None,
                        interpret: bool = False):
    """logits: (B, V) -> (entropy (B, 1) f32, exit (B, 1) int32 0/1).
    B must be a multiple of block_rows (ops.py pads).  ``vocab`` is the true
    (pre-padding) vocabulary: columns at or past it are masked.  ``tau`` is
    a traced (1, 1) float32 scalar living in SMEM — threshold sweeps (the
    paper's Fig. 2 axis) reuse one compilation."""
    B, V = logits.shape
    if B % block_rows != 0:
        raise ValueError(f"unpadded batch: B={B} (logits {logits.shape}) "
                         f"must be a multiple of block_rows={block_rows}; "
                         f"route through ops.entropy_exit for padding")
    vocab = V if vocab is None else vocab
    tau = jnp.asarray(tau, jnp.float32).reshape(1, 1)
    nv = (V + block_v - 1) // block_v
    grid = (B // block_rows, nv)
    kernel = functools.partial(_entropy_kernel, vocab=vocab, block_v=block_v)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((block_rows, block_v), lambda r, iv: (r, iv))],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda r, iv: (r, 0)),
            pl.BlockSpec((block_rows, 1), lambda r, iv: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tau, logits)
