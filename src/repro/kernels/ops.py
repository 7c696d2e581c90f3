"""jit'd public wrappers around the Pallas kernels: shape padding to block
multiples, dtype handling, and an ``interpret`` switch that defaults to True
off-TPU (this container) and False on real TPU.

Forward entry points (``flash_attention``, ``entropy_exit``, ``rwkv_wkv``)
serve every call site; the ``*_fwd``/``*_bwd`` pairs are the training
residual-saving forwards and the hand-written Pallas backwards that
``kernels/dispatch.py`` wires into its ``custom_vjp`` training wrappers —
the padding/layout plumbing (block rounding, zero-padded cotangents, GQA
group reduction, fp32->primal-dtype casts) lives here so the kernels stay
pure block programs."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.entropy_exit import entropy_exit_pallas
from repro.kernels.flash_attention import (LANES,
                                           flash_attention_bwd_dkv_pallas,
                                           flash_attention_bwd_dq_pallas,
                                           flash_attention_pallas)
from repro.kernels.rwkv_wkv import (rwkv_wkv_bwd_pallas, rwkv_wkv_fwd_pallas,
                                    rwkv_wkv_pallas)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, axis, mult):
    if mult <= 0:
        raise ValueError(f"padding multiple must be positive, got {mult} "
                         f"for axis {axis} of shape {x.shape}")
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _check_attention_operands(q, k, v) -> None:
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(
            f"attention operands must be rank-4 (B, H, T, D): got q "
            f"{q.shape}, k {k.shape}, v {v.shape}")
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: k {k.shape} vs v {v.shape}")
    if q.shape[0] != k.shape[0] or q.shape[3] != k.shape[3]:
        raise ValueError(
            f"q/k batch or head-dim mismatch: q {q.shape} vs k {k.shape}")
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(
            f"GQA head mismatch: q has H={q.shape[1]} query heads (shape "
            f"{q.shape}) but k has Hkv={k.shape[1]} kv heads (shape "
            f"{k.shape}); H must be a multiple of Hkv")


def _attn_blocks(q, k, block_q: int, block_k: int):
    Tq, Tk = q.shape[2], k.shape[2]
    return min(block_q, max(Tq, 8)), min(block_k, max(Tk, 8))


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    kv_valid=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, H, Tq, D); k/v: (B, Hkv, Tk, D).  Arbitrary Tq/Tk (padded).
    ``kv_valid`` is an optional traced int32 scalar: keys at
    ``kpos >= kv_valid`` are masked (the decode ring-buffer valid prefix);
    it varies per call without triggering recompilation."""
    interpret = _default_interpret() if interpret is None else interpret
    _check_attention_operands(q, k, v)
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _attn_blocks(q, k, block_q, block_k)
    qp, pq = _pad_to(q, 2, bq)
    kp, _ = _pad_to(k, 2, bk)
    vp, _ = _pad_to(v, 2, bk)
    # the kernel masks padded keys (kpos >= Tk) explicitly, so any
    # causal/window/ragged (Tq != Tk) combination is safe; padded q rows are
    # garbage but sliced off below
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 block_q=bq, block_k=bk, seq_k=Tk,
                                 kv_len=kv_valid, interpret=interpret)
    return out[:, :, :Tq]


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None):
    """The residual-saving training forward: :func:`flash_attention` that
    also returns the per-row logsumexp ``lse (B, H, Tq)`` fp32 — together
    with the output, everything :func:`flash_attention_bwd` needs to avoid
    the reference-VJP forward recompute."""
    interpret = _default_interpret() if interpret is None else interpret
    _check_attention_operands(q, k, v)
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _attn_blocks(q, k, block_q, block_k)
    qp, _ = _pad_to(q, 2, bq)
    kp, _ = _pad_to(k, 2, bk)
    vp, _ = _pad_to(v, 2, bk)
    out, lse = flash_attention_pallas(qp, kp, vp, causal=causal,
                                      window=window, block_q=bq, block_k=bk,
                                      seq_k=Tk, return_lse=True,
                                      interpret=interpret)
    return out[:, :, :Tq], lse[:, :, :Tq, 0]


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None):
    """Fused flash-attention backward from the forward's residuals
    (``o``, ``lse``) and the cotangent ``do`` — dK/dV with the kv-block
    axis outer (GQA group summed in-kernel), dQ with the q-block axis
    outer.  Returns ``(dq, dk, dv)`` cast to the primal dtypes."""
    interpret = _default_interpret() if interpret is None else interpret
    _check_attention_operands(q, k, v)
    if o.shape != q.shape or do.shape != q.shape:
        raise ValueError(f"residual/cotangent shape mismatch: o {o.shape}, "
                         f"do {do.shape}, expected q's shape {q.shape}")
    if lse.shape != q.shape[:3]:
        raise ValueError(f"lse shape mismatch: {lse.shape}, expected "
                         f"{q.shape[:3]}")
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _attn_blocks(q, k, block_q, block_k)
    # delta = rowsum(dO * O): the FA-2 elementwise precompute that lets both
    # backward kernels form dS without re-reducing P rows
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    qp, _ = _pad_to(q, 2, bq)
    kp, _ = _pad_to(k, 2, bk)
    vp, _ = _pad_to(v, 2, bk)
    dop, _ = _pad_to(do.astype(q.dtype), 2, bq)   # padded q rows: do == 0
    # the kernels read the per-row residuals as lane-replicated tiles
    lsep, deltap = (jnp.broadcast_to(_pad_to(a, 2, bq)[0][..., None],
                                     qp.shape[:3] + (LANES,))
                    for a in (lse, delta))
    common = dict(causal=causal, window=window, block_q=bq, block_k=bk,
                  seq_k=Tk, interpret=interpret)
    dk, dv = flash_attention_bwd_dkv_pallas(qp, kp, vp, dop, lsep, deltap,
                                            **common)
    dq = flash_attention_bwd_dq_pallas(qp, kp, vp, dop, lsep, deltap,
                                       **common)
    return (dq[:, :, :Tq].astype(q.dtype), dk[:, :, :Tk].astype(k.dtype),
            dv[:, :, :Tk].astype(v.dtype))


@functools.partial(jax.jit, static_argnames=("block_rows", "block_v",
                                             "interpret"))
def entropy_exit(logits, tau, *, block_rows: int = 8,
                 block_v: int = 2048, interpret: Optional[bool] = None):
    """logits (B, V) -> (entropy (B,), exit_mask (B,) bool).  ``tau`` is a
    traced runtime scalar (float or 0-d array): threshold sweeps reuse one
    compilation, matching ``make_serve_step``'s traced-tau contract."""
    interpret = _default_interpret() if interpret is None else interpret
    if logits.ndim != 2:
        raise ValueError(f"entropy_exit expects rank-2 (B, V) logits, got "
                         f"{logits.shape}")
    B, V = logits.shape
    # rows pad to whole row blocks and the vocab to whole 128-lane vocab
    # blocks (the kernel masks columns >= V), so every block is TPU-legal
    bv = min(block_v, -(-V // 128) * 128)
    xp, _ = _pad_to(logits, 0, block_rows)
    xp, _ = _pad_to(xp, 1, bv)
    H, ex = entropy_exit_pallas(xp, tau, block_rows=block_rows, block_v=bv,
                                vocab=V, interpret=interpret)
    return H[:B, 0], ex[:B, 0].astype(bool)


def _check_wkv_operands(r, k, v, log_w, u) -> None:
    if r.ndim != 4:
        raise ValueError(f"wkv operands must be rank-4 (B, T, H, K): got r "
                         f"{r.shape}")
    for name, a in (("k", k), ("v", v), ("log_w", log_w)):
        if a.shape != r.shape:
            raise ValueError(f"wkv operand shape mismatch: r {r.shape} vs "
                             f"{name} {a.shape}")
    if u.shape != r.shape[2:]:
        raise ValueError(f"wkv bonus shape mismatch: u {u.shape}, expected "
                         f"(H, K) = {r.shape[2:]} from r {r.shape}")


def _wkv_flatten(r, k, v, log_w, u, chunk):
    """Model layout (B, T, H, K) -> padded kernel layout (B*H, Tp, K)."""
    B, T, H, K = r.shape
    ch = min(chunk, T)

    def flat(x):
        x = jnp.moveaxis(x, 2, 1).reshape(B * H, T, K)
        return _pad_to(x, 1, ch)[0]

    uf = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, K)
    return flat(r), flat(k), flat(v), flat(log_w), uf, ch


@functools.partial(jax.jit, static_argnames=("chunk", "return_state",
                                             "interpret"))
def rwkv_wkv(r, k, v, log_w, u, *, chunk: int = 64,
             return_state: bool = False,
             interpret: Optional[bool] = None):
    """r/k/v/log_w: (B, T, H, K); u: (H, K) -> y (B, T, H, K) fp32.
    Arbitrary T (padded; log_w pads to 0 => identity steps).  With
    ``return_state`` also returns the final carried state (B, H, K, K) fp32
    (unaffected by padding: pad steps have decay 1 and k = 0)."""
    interpret = _default_interpret() if interpret is None else interpret
    _check_wkv_operands(r, k, v, log_w, u)
    B, T, H, K = r.shape
    rf, kf, vf, lwf, uf, ch = _wkv_flatten(r, k, v, log_w, u, chunk)
    y, sT = rwkv_wkv_pallas(rf, kf, vf, lwf, uf, chunk=ch,
                            interpret=interpret)
    y = y[:, :T].reshape(B, H, T, K)
    y = jnp.moveaxis(y, 1, 2)
    if return_state:
        return y, sT.reshape(B, H, K, K)
    return y


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_wkv_fwd(r, k, v, log_w, u, *, chunk: int = 64,
                 interpret: Optional[bool] = None):
    """The residual-saving training forward: ``rwkv_wkv(...,
    return_state=True)`` plus the per-chunk entry states ``s0`` (kept in
    the flattened kernel layout ``(B*H, Tp/chunk, K, K)`` — an opaque
    residual handed back to :func:`rwkv_wkv_bwd`)."""
    interpret = _default_interpret() if interpret is None else interpret
    _check_wkv_operands(r, k, v, log_w, u)
    B, T, H, K = r.shape
    rf, kf, vf, lwf, uf, ch = _wkv_flatten(r, k, v, log_w, u, chunk)
    y, sT, s0 = rwkv_wkv_fwd_pallas(rf, kf, vf, lwf, uf, chunk=ch,
                                    interpret=interpret)
    y = jnp.moveaxis(y[:, :T].reshape(B, H, T, K), 1, 2)
    return (y, sT.reshape(B, H, K, K)), s0


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_wkv_bwd(r, k, v, log_w, u, s0, dy, dsT, *, chunk: int = 64,
                 interpret: Optional[bool] = None):
    """Chunked wkv backward from the saved per-chunk states: cotangents
    ``dy (B, T, H, K)`` / ``dsT (B, H, K, K)`` -> ``(dr, dk, dv, dlog_w,
    du)`` cast to the primal dtypes, ``du`` reduced over the batch."""
    interpret = _default_interpret() if interpret is None else interpret
    _check_wkv_operands(r, k, v, log_w, u)
    B, T, H, K = r.shape
    rf, kf, vf, lwf, uf, ch = _wkv_flatten(r, k, v, log_w, u, chunk)
    dyf = _pad_to(jnp.moveaxis(dy.astype(jnp.float32), 2, 1)
                  .reshape(B * H, T, K), 1, ch)[0]    # pad tokens: dy == 0
    dsTf = dsT.astype(jnp.float32).reshape(B * H, K, K)
    dr, dk, dv, dlw, du = rwkv_wkv_bwd_pallas(rf, kf, vf, lwf, uf, dyf, s0,
                                              dsTf, chunk=ch,
                                              interpret=interpret)

    def unflat(x):
        return jnp.moveaxis(x[:, :T].reshape(B, H, T, K), 1, 2)

    return (unflat(dr).astype(r.dtype), unflat(dk).astype(k.dtype),
            unflat(dv).astype(v.dtype), unflat(dlw).astype(log_w.dtype),
            du.reshape(B, H, K).sum(0).astype(u.dtype))
