"""Chunked RWKV6 WKV scan (Pallas, TPU target), forward AND backward.

TPU adaptation of the Finch CUDA kernel (docs/DESIGN.md §2): instead of one thread
per channel marching token-by-token (GPU-shaped), we process the sequence in
chunks — quadratic MXU work inside a chunk plus a VMEM-resident recurrent
state (K x V per head) carried across sequential grid steps.  Per chunk, with
per-channel cumulative log-decay L_t = sum_{j<=t} log w_j:

    y_t  = (r_t * e^{L_{t-1}}) . S_chunkstart                 (inter)
         + sum_{i<t} (r_t * e^{L_{t-1}-L_i}) . k_i  v_i       (intra)
         + (r_t * u * k_t) . v_t                              (bonus diag)
    S'   = diag(e^{L_Q}) S + sum_i (k_i e^{L_Q - L_i}) v_i^T

Grid = (B*H, T/Q); the second axis is sequential so S lives in scratch.
The per-row bonus ``u`` (and its gradient ``du``) travels as ``(B*H, 1, K)``
so its ``(1, K)`` block spans the array's last two dims, as Mosaic requires.

The backward (:func:`rwkv_wkv_bwd_pallas`) mirrors the chunk structure in
REVERSE: the training forward (:func:`rwkv_wkv_fwd_pallas`) additionally
saves every chunk's *entry* state S_c, and the backward grid walks chunks
last-to-first (a flipped index map) carrying the state adjoint
G_c = dLoss/dS'_c in scratch.  Per chunk it replays the closed-form chunk
algebra adjointly — no token-level scan anywhere — producing
dr/dk/dv/dlog_w for the chunk's tokens plus a du accumulator, and updates

    G_{c-1} = diag(e^{L_Q}) G_c + rw_c^T dy_c

so one sequential sweep prices the whole recurrence's gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cumsum_rows(x):
    """Inclusive prefix sum down the rows of a (Q, K) tile: log2(Q) rounds
    of add-the-rows-``s``-above (Hillis-Steele), because Mosaic has no
    lowering for ``cumsum``."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    s = 1
    while s < x.shape[0]:
        x = x + jnp.where(row >= s, pltpu.roll(x, s, 0), 0.0)
        s *= 2
    return x


def _chunk_decays(lw):
    """Per-chunk decay tensors from the chunk's log-decays (Q, K):
    cumulative L, previous-token L, and the chunk-exit vector e^{L_Q}."""
    L = _cumsum_rows(lw)                                      # (Q, K)
    L_prev = L - lw
    return L, L_prev


def _chunk_fwd(r, k, v, lw, u, S):
    """One chunk of the wkv recurrence (all fp32): inputs (Q, K) plus the
    carried state S (K, K); returns ``(y, S')``."""
    L, L_prev = _chunk_decays(lw)
    rw = r * jnp.exp(L_prev)                                  # decayed queries
    kw = k * jnp.exp(-L)                                      # advanced keys

    # intra-chunk, strictly-lower-triangular scores
    scores = jax.lax.dot_general(rw, kw, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    ti = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(tj < ti, scores, 0.0)
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # bonus diagonal (current token, no decay, u-weighted)
    y += jnp.sum(r * u * k, axis=1, keepdims=True) * v
    # inter-chunk from the carried state
    y += jax.lax.dot_general(rw, S, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: S' = diag(e^{L_Q}) S + sum_i (k_i e^{L_Q - L_i}) v_i^T
    LQ = L[-1:, :]                                            # (1, K)
    tail = jnp.exp(LQ - L)                                    # (Q, K)
    s_new = (jnp.exp(LQ).T * S
             + jax.lax.dot_general(k * tail, v, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    return y, s_new


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, sT_ref, *rest,
                chunk: int, emit_chunk_states: bool):
    if emit_chunk_states:
        s0_ref, s_scr = rest
    else:
        (s_scr,) = rest
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    if emit_chunk_states:
        s0_ref[0, 0, ...] = s_scr[...]                        # entry state

    r = r_ref[0].astype(jnp.float32)                          # (Q, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)                        # log w_t <= 0
    u = u_ref[0].astype(jnp.float32)                          # (1, K) bonus

    y, s_new = _chunk_fwd(r, k, v, lw, u, s_scr[...])
    y_ref[0, ...] = y.astype(y_ref.dtype)
    s_scr[...] = s_new

    @pl.when(ic == nc - 1)
    def _emit_state():
        sT_ref[0, ...] = s_new                                # carry (K, K)


def _check_wkv_shapes(r, k, v, log_w, u, chunk: int) -> None:
    BH, T, K = r.shape
    for name, a in (("k", k), ("v", v), ("log_w", log_w)):
        if a.shape != r.shape:
            raise ValueError(f"wkv operand shape mismatch: r {r.shape} vs "
                             f"{name} {a.shape}")
    if u.shape != (BH, K):
        raise ValueError(f"wkv bonus shape mismatch: u {u.shape}, expected "
                         f"(BH, K) = {(BH, K)} to match r {r.shape}")
    if T % chunk != 0:
        raise ValueError(
            f"unpadded sequence length: T={T} (r shape {r.shape}) must be a "
            f"multiple of chunk={chunk}; route through ops.rwkv_wkv for "
            f"automatic padding")


def rwkv_wkv_pallas(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    log_w: jnp.ndarray, u: jnp.ndarray, *,
                    chunk: int = 64, interpret: bool = False):
    """r/k/v/log_w: (BH, T, K) flattened batch*heads; u: (BH, K).
    T must be a multiple of ``chunk`` (ops.py pads).  Returns
    ``(y (BH, T, K), S_T (BH, K, K))`` — the outputs plus the final carried
    state, so chunked prefill can seed the decode cache."""
    return _wkv_call(r, k, v, log_w, u, chunk=chunk, interpret=interpret,
                     emit_chunk_states=False)


def rwkv_wkv_fwd_pallas(r, k, v, log_w, u, *, chunk: int = 64,
                        interpret: bool = False):
    """The training forward: :func:`rwkv_wkv_pallas` plus every chunk's
    entry state ``S0 (BH, T/chunk, K, K)`` — the residual the backward
    kernel replays chunks from.  Returns ``(y, S_T, S0)``."""
    return _wkv_call(r, k, v, log_w, u, chunk=chunk, interpret=interpret,
                     emit_chunk_states=True)


def _wkv_call(r, k, v, log_w, u, *, chunk: int, interpret: bool,
              emit_chunk_states: bool):
    BH, T, K = r.shape
    _check_wkv_shapes(r, k, v, log_w, u, chunk)
    nc = T // chunk
    grid = (BH, nc)
    kernel = functools.partial(_wkv_kernel, chunk=chunk,
                               emit_chunk_states=emit_chunk_states)
    seq_spec = pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0))
    out_specs = [seq_spec,
                 pl.BlockSpec((1, K, K), lambda b, c: (b, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((BH, T, K), jnp.float32),
                 jax.ShapeDtypeStruct((BH, K, K), jnp.float32)]
    if emit_chunk_states:
        out_specs.append(pl.BlockSpec((1, 1, K, K),
                                      lambda b, c: (b, c, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH, nc, K, K), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
    )(r, k, v, log_w, u.reshape(BH, 1, K))
    return tuple(out) if emit_chunk_states else (out[0], out[1])


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _wkv_bwd_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, dy_ref, s0_ref,
                    dsT_ref, dr_ref, dk_ref, dv_ref, dlw_ref, du_ref, g_scr,
                    *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        # grid step 0 is the LAST chunk: seed the state adjoint with the
        # cotangent of the returned carry S_T, and zero the du accumulator
        g_scr[...] = dsT_ref[0].astype(jnp.float32)
        du_ref[...] = jnp.zeros_like(du_ref)

    r = r_ref[0].astype(jnp.float32)                          # (Q, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                          # (1, K)
    dy = dy_ref[0].astype(jnp.float32)                        # (Q, K)
    S0 = s0_ref[0, 0]                                         # entry state
    G = g_scr[...]                                            # dL/dS' (K, K)

    L, L_prev = _chunk_decays(lw)
    eLp = jnp.exp(L_prev)
    eLn = jnp.exp(-L)
    rw = r * eLp
    kw = k * eLn
    LQ = L[-1:, :]                                            # (1, K)
    eLQ = jnp.exp(LQ)
    tail = jnp.exp(LQ - L)                                    # (Q, K)

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = tj < ti

    # --- state path: S' = diag(eLQ) S0 + (k * tail)^T v ---
    d_kt = jax.lax.dot_general(v, G, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (Q, K)
    dk_state = tail * d_kt
    d_tail = k * d_kt
    dv_state = jax.lax.dot_general(k * tail, G, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    # --- output path: y = scores v + (r.u.k) v + rw S0 ---
    scores = jax.lax.dot_general(rw, kw, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(lower, scores, 0.0)
    d_scores = jax.lax.dot_general(dy, v, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    d_scores = jnp.where(lower, d_scores, 0.0)
    dv_intra = jax.lax.dot_general(scores, dy, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    d_rw = (jax.lax.dot_general(d_scores, kw, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(dy, S0, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32))
    d_kw = jax.lax.dot_general(d_scores, rw, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    db = jnp.sum(dy * v, axis=1, keepdims=True)               # (Q, 1)
    b = jnp.sum(r * u * k, axis=1, keepdims=True)

    dr = d_rw * eLp + u * k * db
    dk = d_kw * eLn + dk_state + u * r * db
    dv = dv_intra + b * dy + dv_state
    du_c = jnp.sum(r * k * db, axis=0, keepdims=True)         # (1, K)

    # --- log-decay path: L = cumsum(lw); L_Q = L[-1] feeds eLQ and tail ---
    dLQ = (eLQ * jnp.sum(G * S0, axis=1, keepdims=True).T
           + jnp.sum(d_tail * tail, axis=0, keepdims=True))   # (1, K)
    dL = -d_kw * kw - d_tail * tail                           # (Q, K)
    dL_prev = d_rw * rw
    A = dL + dL_prev
    row = jax.lax.broadcasted_iota(jnp.int32, A.shape, 0)
    A = A + jnp.where(row == chunk - 1, dLQ, 0.0)
    # dlw_t = sum_{i>=t} A_i - dL_prev_t  (L_prev_t excludes its own lw_t)
    revcum = jnp.sum(A, axis=0, keepdims=True) - _cumsum_rows(A) + A
    dlw = revcum - dL_prev

    dr_ref[0, ...] = dr
    dk_ref[0, ...] = dk
    dv_ref[0, ...] = dv
    dlw_ref[0, ...] = dlw
    du_ref[0, ...] += du_c

    # adjoint of this chunk's ENTRY state = previous chunk's exit adjoint
    g_scr[...] = (eLQ.T * G
                  + jax.lax.dot_general(rw, dy, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))


def rwkv_wkv_bwd_pallas(r, k, v, log_w, u, dy, s0, dsT, *, chunk: int = 64,
                        interpret: bool = False):
    """Chunked wkv backward.  r/k/v/log_w/dy: (BH, T, K); u: (BH, K);
    ``s0``: the forward's per-chunk entry states (BH, T/chunk, K, K);
    ``dsT``: cotangent of the final carried state (BH, K, K).  Walks the
    chunk grid in reverse (flipped index maps) with the state adjoint in
    scratch.  Returns ``(dr, dk, dv, dlog_w, du)`` all fp32, ``du``
    accumulated over the sequence per (BH,) row."""
    BH, T, K = r.shape
    _check_wkv_shapes(r, k, v, log_w, u, chunk)
    nc = T // chunk
    if s0.shape != (BH, nc, K, K):
        raise ValueError(f"chunk-state residual shape mismatch: s0 "
                         f"{s0.shape}, expected {(BH, nc, K, K)}")
    grid = (BH, nc)
    kernel = functools.partial(_wkv_bwd_kernel, chunk=chunk)
    # reversed chunk order: grid step c touches chunk nc-1-c
    rev_seq = pl.BlockSpec((1, chunk, K), lambda b, c: (b, nc - 1 - c, 0))
    bh_vec = pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[rev_seq, rev_seq, rev_seq, rev_seq, bh_vec, rev_seq,
                  pl.BlockSpec((1, 1, K, K), lambda b, c: (b, nc - 1 - c,
                                                           0, 0)),
                  pl.BlockSpec((1, K, K), lambda b, c: (b, 0, 0))],
        out_specs=[rev_seq, rev_seq, rev_seq, rev_seq, bh_vec],
        out_shape=[jax.ShapeDtypeStruct((BH, T, K), jnp.float32)] * 4
        + [jax.ShapeDtypeStruct((BH, 1, K), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
    )(r, k, v, log_w, u.reshape(BH, 1, K), dy, s0, dsT)
    return tuple(outs[:4]) + (outs[4].reshape(BH, K),)
