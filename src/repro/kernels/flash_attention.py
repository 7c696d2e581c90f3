"""Block-tiled causal flash attention (Pallas, TPU target), forward AND
backward.

Forward — canonical TPU pattern: grid = (B, H, num_q_blocks, num_kv_blocks)
with the last grid axis sequential; running (max, denom, accum) live in VMEM
scratch across kv-block steps.  GQA is native — the k/v BlockSpec index maps
query head h to kv head h * Hkv // H, so grouped heads re-read the same kv
block (a local revisit, no HBM duplication).  Sliding windows skip blocks
entirely outside the band via ``pl.when``.  With ``return_lse`` the kernel
additionally emits the per-row logsumexp of the scaled scores — the residual
the backward kernels need to rebuild P without re-running the softmax
reduction.

Backward — the standard two-kernel recompute-of-P-from-LSE scheme:

  * dK/dV (:func:`flash_attention_bwd_dkv_pallas`): grid =
    (B, Hkv, num_kv_blocks, G, num_q_blocks) — the kv-block axis is OUTER
    and the (group, q-block) axes inner-sequential, so dK/dV blocks stay
    resident in VMEM while every query head of the GQA group and every q
    block stream past (grouped dK/dV accumulation happens *inside* the
    kernel; the output is already (B, Hkv, Tk, D)).
  * dQ (:func:`flash_attention_bwd_dq_pallas`): grid =
    (B, H, num_q_blocks, num_kv_blocks) — q-block axis outer, kv blocks
    stream while dQ accumulates in its output block.

Both rebuild P = exp(s - lse) per block pair, re-applying every forward
mask path (causal, sliding window, ragged ``seq_k`` padding) so masked
entries are exactly zero — that also hard-zeroes fully-masked rows instead
of the exp(0)/denom blow-up a mask-free rebuild would produce.  dS =
P * (dP - delta) with delta = rowsum(dO * O) precomputed host-side (one
cheap elementwise pass, the FA-2 split).

Default blocks (128, 128): MXU-aligned (contracting/lane dims multiples of
128); working set 4 x 128x128 x 4B ~= 256 KiB << 16 MiB VMEM, leaving head
room for double buffering.

Per-row quantities (running max/denominator, the saved logsumexp, the
backward's delta) are carried lane-replicated as ``(rows, LANES)`` tiles:
Mosaic only accepts blocks whose last two dims are (8, 128)-aligned or
whole, so a rank-1 ``(block_q,)`` row block cannot be lowered for the TPU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: lane width of the per-row (max, denominator, lse, delta) tiles
LANES = 128


def _block_run(k_start, q_start, *, block_q: int, block_k: int,
               causal: bool, window: Optional[int], seq_k: int):
    """Whether a (q-block, kv-block) pair has any unmasked entry: not
    entirely above the causal diagonal, not entirely left of the sliding
    window, not entirely inside the key padding."""
    run = k_start < seq_k
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k > q_start - window + 1)
    return run


def _pair_mask(shape, q_start, k_start, *, causal: bool,
               window: Optional[int], seq_k: int):
    """The forward's element mask for one (q-block, kv-block) score tile."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _flash_kernel(kv_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                  scale: float, block_q: int, block_k: int,
                  causal: bool, window: Optional[int], seq_k: int,
                  emit_lse: bool):
    if emit_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    kv_len = kv_ref[0, 0]                                    # traced valid-prefix

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # block-level skip: entirely above the causal diagonal, entirely left of
    # the sliding window, entirely inside the key padding, or entirely past
    # the traced valid prefix (decode ring buffers attend kpos < kv_len).
    run = jnp.logical_and(
        _block_run(k_start, q_start, block_q=block_q, block_k=block_k,
                   causal=causal, window=window, seq_k=seq_k),
        k_start < kv_len)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (Bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (Bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # padded keys are masked unconditionally — the causal diagonal only
        # covers them when Tq == Tk, and non-causal shapes (the ServeSession
        # decode path, Tq != Tk) have no diagonal at all
        mask = _pair_mask(s.shape, q_start, k_start, causal=causal,
                          window=window, seq_k=seq_k)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.logical_and(mask, kpos < kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                  # (Bq, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha[:, :1]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / denom[:, :1]).astype(o_ref.dtype)
        if emit_lse:
            lse_ref[0, 0, ...] = m_scr[...] + jnp.log(denom)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128,
                           seq_k: Optional[int] = None,
                           kv_len: Optional[jnp.ndarray] = None,
                           return_lse: bool = False,
                           interpret: bool = False):
    """q: (B, H, Tq, D); k/v: (B, Hkv, Tk, D), H % Hkv == 0.  Tq/Tk must be
    multiples of the block sizes (ops.py pads arbitrary shapes); ``seq_k``
    is the true (pre-padding) key length — keys at ``kpos >= seq_k`` are
    masked inside the kernel regardless of the causal/window setting.
    ``kv_len`` is an optional *traced* int32 scalar masking keys at
    ``kpos >= kv_len`` on top of the static masks — the decode ring-buffer
    valid prefix, varying per step without recompilation.  With
    ``return_lse`` also returns the per-row logsumexp of the scaled scores
    (B, H, Tq, LANES) fp32, replicated across the lanes — the backward
    kernels' residual."""
    B, H, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    _check_qkv_shapes(q, k, v, block_q, block_k)
    seq_k = Tk if seq_k is None else seq_k
    if not 0 < seq_k <= Tk:
        raise ValueError(
            f"seq_k={seq_k} out of range: must satisfy 0 < seq_k <= Tk={Tk} "
            f"(k shape {k.shape})")
    kv_len = (jnp.full((1, 1), seq_k, jnp.int32) if kv_len is None
              else jnp.asarray(kv_len, jnp.int32).reshape(1, 1))
    scale = 1.0 / math.sqrt(D)
    grid = (B, H, Tq // block_q, Tk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, seq_k=seq_k, emit_lse=return_lse)

    kv_index = lambda b, h, iq, ik: (b, h * Hkv // H, ik, 0)
    out_specs = [pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, iq, ik: (b, h, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec((1, 1, block_q, LANES),
                                      lambda b, h, iq, ik: (b, h, iq, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, Tq, LANES),
                                              jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, q, k, v)
    return tuple(out) if return_lse else out[0]


def _check_qkv_shapes(q, k, v, block_q: int, block_k: int) -> None:
    B, H, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    if H % Hkv != 0:
        raise ValueError(
            f"GQA head mismatch: q has H={H} query heads (shape {q.shape}) "
            f"but k has Hkv={Hkv} kv heads (shape {k.shape}); H must be a "
            f"multiple of Hkv")
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: k {k.shape} vs v {v.shape}")
    if k.shape[0] != B or k.shape[3] != D:
        raise ValueError(
            f"q/k batch or head-dim mismatch: q {q.shape} vs k {k.shape}")
    if Tq % block_q != 0 or Tk % block_k != 0:
        raise ValueError(
            f"unpadded sequence lengths: Tq={Tq} must be a multiple of "
            f"block_q={block_q} and Tk={Tk} of block_k={block_k} "
            f"(q {q.shape}, k {k.shape}); route through ops.flash_attention "
            f"for automatic padding")


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _rebuild_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  q_start, k_start, *, scale, causal, window, seq_k):
    """Recompute the probability tile P from the saved LSE and form
    dS = P * (dP - delta) * scale for one (q-block, kv-block) pair —
    the shared core of both backward kernels."""
    q = q_ref[0, 0].astype(jnp.float32)                      # (Bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                      # (Bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, :1].astype(jnp.float32)           # (Bq, 1)
    delta = delta_ref[0, 0][:, :1].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _pair_mask(s.shape, q_start, k_start, causal=causal,
                      window=window, seq_k=seq_k)
    # exact-zero masked entries: padded q rows (do == 0) and fully-masked
    # rows contribute nothing, with no exp blow-up from a garbage lse
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)               # (Bq, Bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return q, k, do, p, ds


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, scale: float, block_q: int,
                          block_k: int, causal: bool, window: Optional[int],
                          seq_k: int):
    ik = pl.program_id(2)
    ig = pl.program_id(3)
    iq = pl.program_id(4)

    @pl.when(jnp.logical_and(ig == 0, iq == 0))
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    run = _block_run(k_start, q_start, block_q=block_q, block_k=block_k,
                     causal=causal, window=window, seq_k=seq_k)

    @pl.when(run)
    def _compute():
        q, _, do, p, ds = _rebuild_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
            k_start, scale=scale, causal=causal, window=window, seq_k=seq_k)
        # grouped accumulation: this (b, hkv, ik) output block stays
        # resident across the whole (group, q-block) inner sweep
        dv_ref[0, 0, ...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_ref[0, 0, ...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, scale: float, block_q: int, block_k: int,
                         causal: bool, window: Optional[int], seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    run = _block_run(k_start, q_start, block_q=block_q, block_k=block_k,
                     causal=causal, window=window, seq_k=seq_k)

    @pl.when(run)
    def _compute():
        _, k, _, _, ds = _rebuild_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
            k_start, scale=scale, causal=causal, window=window, seq_k=seq_k)
        dq_ref[0, 0, ...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def flash_attention_bwd_dkv_pallas(q, k, v, do, lse, delta, *,
                                   causal: bool = True,
                                   window: Optional[int] = None,
                                   block_q: int = 128, block_k: int = 128,
                                   seq_k: Optional[int] = None,
                                   interpret: bool = False):
    """dK/dV: kv-block axis outer, (GQA group x q-block) inner-sequential.
    ``lse``/``delta`` are (B, H, Tq, LANES) fp32, lane-replicated — the
    forward's logsumexp and rowsum(dO * O).  Returns ``(dk, dv)`` in (B, Hkv, Tk, D) fp32, the
    GQA group already summed in-kernel."""
    B, H, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    _check_qkv_shapes(q, k, v, block_q, block_k)
    G = H // Hkv
    seq_k = Tk if seq_k is None else seq_k
    scale = 1.0 / math.sqrt(D)
    grid = (B, Hkv, Tk // block_k, G, Tq // block_q)

    kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, block_q=block_q,
        block_k=block_k, causal=causal, window=window, seq_k=seq_k)

    q_index = lambda b, hkv, ik, ig, iq: (b, hkv * G + ig, iq, 0)
    row_index = lambda b, hkv, ik, ig, iq: (b, hkv * G + ig, iq, 0)
    kv_index = lambda b, hkv, ik, ig, iq: (b, hkv, ik, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_q, LANES), row_index),
            pl.BlockSpec((1, 1, block_q, LANES), row_index),
        ],
        out_specs=[pl.BlockSpec((1, 1, block_k, D), kv_index),
                   pl.BlockSpec((1, 1, block_k, D), kv_index)],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Tk, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, Tk, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def flash_attention_bwd_dq_pallas(q, k, v, do, lse, delta, *,
                                  causal: bool = True,
                                  window: Optional[int] = None,
                                  block_q: int = 128, block_k: int = 128,
                                  seq_k: Optional[int] = None,
                                  interpret: bool = False):
    """dQ: q-block axis outer, kv blocks streaming while dQ accumulates in
    its resident output block.  Returns dq (B, H, Tq, D) fp32."""
    B, H, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    _check_qkv_shapes(q, k, v, block_q, block_k)
    seq_k = Tk if seq_k is None else seq_k
    scale = 1.0 / math.sqrt(D)
    grid = (B, H, Tq // block_q, Tk // block_k)

    kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, seq_k=seq_k)

    q_index = lambda b, h, iq, ik: (b, h, iq, 0)
    row_index = lambda b, h, iq, ik: (b, h, iq, 0)
    kv_index = lambda b, h, iq, ik: (b, h * Hkv // H, ik, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_q, LANES), row_index),
            pl.BlockSpec((1, 1, block_q, LANES), row_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_index),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), jnp.float32),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
