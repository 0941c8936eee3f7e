"""Fused Tryage routing head.

The routing decision is latency-critical (it sits in front of every
request) and tiny: pooled embedding (B, d) -> gelu MLP -> softplus ->
predicted losses (B, M) -> + lambda-weighted constraints -> argmin.  Done
naively that is four kernel launches and three HBM round-trips of (B, M)
intermediates.  Here the whole head runs in one Pallas program per batch
tile: both matmuls hit the MXU from VMEM-resident weights (d, hidden and M
are small), and the constraint-add + argmin happen in VREGs.  Outputs are
the scores (for telemetry/Pareto sweeps) and the selected expert index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret


def _router_kernel(emb_ref, w1_ref, b1_ref, w2_ref, b2_ref, cvals_ref,
                   lam_ref, scores_ref, choice_ref):
    emb = emb_ref[...].astype(jnp.float32)               # (bb, d)
    h = jax.lax.dot_general(emb, w1_ref[...],
                            (((1,), (0,)), ((), ()))) + b1_ref[...]
    h = jax.nn.gelu(h)
    raw = jax.lax.dot_general(h, w2_ref[...],
                              (((1,), (0,)), ((), ()))) + b2_ref[...]
    pred = jax.nn.softplus(raw)                          # (bb, M)
    scores_ref[...] = pred
    # constraint add: lam (bb, n_c), cvals (n_c, M)
    combined = pred + jax.lax.dot_general(
        lam_ref[...].astype(jnp.float32), cvals_ref[...],
        (((1,), (0,)), ((), ())))
    # (bb, 1) column: a 1-D (bb,) output block mismatches XLA's tiling of
    # the s32[Bp] result once the grid has several tiles
    choice_ref[...] = jnp.argmin(combined, axis=1,
                                 keepdims=True).astype(jnp.int32)


def launch_plan(B: int, block_b: int) -> dict:
    """Effective launch geometry for a batch-tiled routing kernel.

    ``block_b`` is silently clamped to the batch (a tile larger than B
    would be all padding), so the *requested* tile and the tile that
    actually ran can differ.  This is the single source of truth both
    kernels and the autotuner use: tile-table entries record
    ``effective_block_b`` from here, so they cannot lie about what ran.

    Returns ``{"block_b": effective tile, "padded_batch": B + pad,
    "grid": padded_batch // effective tile}``.
    """
    eff = max(1, min(int(block_b), int(B)))
    padded = B + (-B) % eff
    return {"block_b": eff, "padded_batch": padded, "grid": padded // eff}


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def router_score_fused(emb, w1, b1, w2, b2, cvals, lam, *, block_b=128,
                       interpret=None):
    """emb (B, d); cvals (n_c, M); lam (B, n_c).

    Returns (pred_losses (B, M) f32, choice (B,) int32).  ``interpret=None``
    picks compiled on TPU/GPU, interpret on CPU.
    """
    interpret = default_interpret(interpret)
    B, d = emb.shape
    M = w2.shape[1]
    n_c = cvals.shape[0]
    plan = launch_plan(B, block_b)
    block_b = plan["block_b"]
    pad = plan["padded_batch"] - B
    if pad:
        emb = jnp.pad(emb, ((0, pad), (0, 0)))
        lam = jnp.pad(lam, ((0, pad), (0, 0)))
    Bp = emb.shape[0]
    hidden = w1.shape[1]
    scores, choice = pl.pallas_call(
        _router_kernel,
        grid=(plan["grid"],),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((d, hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
            pl.BlockSpec((hidden, M), lambda i: (0, 0)),
            pl.BlockSpec((M,), lambda i: (0,)),
            pl.BlockSpec((n_c, M), lambda i: (0, 0)),
            pl.BlockSpec((block_b, n_c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, M), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(emb, w1, b1, w2, b2, cvals, lam)
    return scores[:B], choice[:B, 0]
