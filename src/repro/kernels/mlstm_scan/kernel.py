"""Chunkwise-parallel mLSTM recurrence (xLSTM) in Pallas.

TPU adaptation of the xLSTM paper's fused CUDA recurrence: instead of a
per-timestep sequential loop (VPU-bound, no MXU work), the sequence is
processed in chunks of L timesteps.  Within a chunk the recurrence has a
closed form:

  lf_t = logsigmoid(f_t);  F_t = cumsum(lf)_t  (inclusive)
  g_t  = cummax(i_s - F_s)_t
  m_t  = F_t + max(m_prev, g_t)                       (stabilizer)
  num_t = e^{F_t + m_prev - m_t} q_t C_prev
        + sum_{s<=t} e^{F_t - F_s + i_s - m_t} (q_t.k_s) v_s
  den_t = e^{F_t + m_prev - m_t} q_t.n_prev
        + sum_{s<=t} e^{F_t - F_s + i_s - m_t} (q_t.k_s)
  h_t  = num_t / max(|den_t|, e^{-m_t})

so the inner sums become two (L,L)x(L,dh) matmuls on the MXU.  The grid is
(batch*heads,); a fori_loop walks chunks carrying (C, n, m) in VREG/VMEM.
Matches the sequential oracle (ref.py) to ~1e-5.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, c0_ref, n0_ref, m0_ref,
                  h_ref, c1_ref, n1_ref, m1_ref, *, chunk, seq_len):
    """Every value stays 2-D — per-timestep vectors as (L, 1) columns or
    (1, L) rows, the carried stabilizer as a (1, dh) row — and the scans
    are masked (L, L) reductions: the TPU lowering has no cumsum and
    handles 1-D vectors and scalar carries poorly."""
    dh = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    n_chunks = seq_len // chunk
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = s_idx <= t_idx                               # [t, s]: s <= t
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, dh), 0) == chunk - 1

    def row(col):
        # (L, 1) -> (1, L) without a transpose: pick the diagonal
        return jnp.sum(jnp.where(s_idx == t_idx, col, 0.0), axis=0,
                       keepdims=True)

    def body(ci, carry):
        # m rides as a (1, dh) row of equal values: Mosaic cannot
        # broadcast a (1, 1) over both sublanes and lanes (decay * C)
        C, n, m_row = carry                               # (dh,dh),(1,dh),(1,dh)
        m = m_row[:, :1]                                  # (1, 1)
        # leading dim indexed with pl.ds(0, 1), not a python int: interpret
        # mode's load/store discharge rejects scalar ints inside fori_loop
        t0 = pl.multiple_of(ci * chunk, chunk)
        sl = (pl.ds(0, 1), pl.ds(t0, chunk), slice(None))
        q = q_ref[sl][0] * scale                          # (L, dh)
        k = k_ref[sl][0]
        v = v_ref[sl][0]
        ig_c = i_ref[sl][0]                               # (L, 1)
        lf_c = jax.nn.log_sigmoid(f_ref[sl][0])
        ig, lf = row(ig_c), row(lf_c)                     # (1, L)

        # inclusive prefix sum F, as a column and as a row
        F_c = jnp.sum(jnp.where(causal, lf, 0.0), axis=1, keepdims=True)
        F_r = jnp.sum(jnp.where(t_idx <= s_idx, lf_c, 0.0), axis=0,
                      keepdims=True)
        # g_t = max_{s<=t} (i_s - F_s)
        g = jnp.max(jnp.where(causal, ig - F_r, NEG_INF), axis=1,
                    keepdims=True)
        m_t = F_c + jnp.maximum(m, g)                     # (L, 1)

        # inter-chunk term
        w_inter = jnp.exp(F_c + m - m_t)                  # (L, 1)
        qC = jax.lax.dot_general(q, C, (((1,), (0,)), ((), ())))  # (L, dh)
        num = w_inter * qC
        den = w_inter * jnp.sum(q * n, axis=1, keepdims=True)

        # intra-chunk term: W[t,s] = exp(F_t - F_s + i_s - m_t), s <= t
        logw = (F_c - m_t) + (ig - F_r)                   # (L, L)
        W = jnp.exp(jnp.where(causal, logw, NEG_INF))
        S = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (L, L)
        WS = W * S
        num = num + jax.lax.dot_general(WS, v, (((1,), (0,)), ((), ())))
        den = den + jnp.sum(WS, axis=1, keepdims=True)

        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
        h_ref[sl] = h[None].astype(h_ref.dtype)

        # end-of-chunk state
        # row L-1 of each column, spread over dh lanes
        m_last = jnp.sum(jnp.where(last, m_t, 0.0), axis=0, keepdims=True)
        F_last = jnp.sum(jnp.where(last, F_c, 0.0), axis=0, keepdims=True)
        kw = k * jnp.exp((F_last[:, :1] - F_c) + ig_c - m_last[:, :1])
        decay = jnp.exp(F_last + m_row - m_last)          # (1, dh)
        C_new = decay * C + jax.lax.dot_general(
            kw, v, (((0,), (0,)), ((), ())))
        n_new = decay * n + jnp.sum(kw, axis=0, keepdims=True)
        return C_new, n_new, m_last

    C0 = c0_ref[0].astype(jnp.float32)
    n0 = n0_ref[0].astype(jnp.float32)                    # (1, dh)
    m0 = jnp.broadcast_to(m0_ref[0].astype(jnp.float32), (1, dh))
    C, n, m = jax.lax.fori_loop(0, n_chunks, body, (C0, n0, m0))
    c1_ref[0] = C.astype(c1_ref.dtype)
    n1_ref[0] = n.astype(n1_ref.dtype)
    m1_ref[0] = m[:, :1].astype(m1_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise_bh(q, k, v, i_pre, f_pre, C0, n0, m0, *, chunk=64,
                       interpret=None):
    """q/k/v: (BH, S, dh) f32; i/f: (BH, S); C0 (BH, dh, dh); n0 (BH, dh);
    m0 (BH,).  Returns (h (BH, S, dh), C1, n1, m1)."""
    interpret = default_interpret(interpret)
    BH, S, dh = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    # gates ride as (BH, S, 1) columns and n/m as (BH, 1, n) rows, so
    # every block's last two dims equal the array's (the TPU lowering's
    # tiling rule) and chunk slices move along sublanes, not lanes
    kernel = functools.partial(_mlstm_kernel, chunk=chunk, seq_len=S)
    h, C1, n1, m1 = pl.pallas_call(
        kernel,
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, S, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, S, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, S, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, S, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, S, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dh, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dh, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, dh), jnp.float32),
            jax.ShapeDtypeStruct((BH, dh, dh), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, dh), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, i_pre[..., None], f_pre[..., None], C0, n0[:, None],
      m0[:, None, None])
    return h, C1, n1[:, 0], m1[:, 0, 0]
