"""Block-wise online-softmax attention (FlashAttention) in Pallas.

TPU adaptation: the grid is (batch*heads, q-blocks); each program holds a
(block_q, head_dim) query tile in VMEM and streams K/V tiles of
(block_k, head_dim) through VMEM with a fori_loop, maintaining the online
softmax (running max m, normalizer l, accumulator acc) in VREGs.  Block
sizes default to 128 — MXU-aligned on both matmul dims.  Causal masking,
sliding windows and logit softcap (Grok) are folded into the inner loop.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

NEG_INF = -2.3819763e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window,
                 softcap, block_q, block_k, seq_len_kv):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (bq, hd)
    bq, hd = q.shape
    n_kb = seq_len_kv // block_k

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(kb, carry):
        m, l, acc = carry
        # leading dim indexed with pl.ds(0, 1), not a python int: interpret
        # mode's load discharge rejects scalar int indices inside fori_loop
        kv = (pl.ds(0, 1), pl.ds(kb * block_k, block_k), slice(None))
        k = k_ref[kv][0].astype(jnp.float32)
        v = v_ref[kv][0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        ok = jnp.ones((bq, block_k), jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window > 0:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = corr * l + p.sum(axis=1)
        acc_new = corr[:, None] * acc + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=0, softcap=0.0,
                         block_q=128, block_k=128, interpret=None):
    """q/k/v: (BH, S, hd) with identical head counts. Returns (BH, S, hd)."""
    interpret = default_interpret(interpret)
    BH, S, hd = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, seq_len_kv=T)
    return pl.pallas_call(
        kernel,
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, T, hd), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, T, hd), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
