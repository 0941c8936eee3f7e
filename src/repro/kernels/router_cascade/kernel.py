"""One-launch cascade decision: scores + confidence + depth-1 escalation.

``router_score_fused`` resolves the single-shot decision in one Pallas
program, but cascade traffic (requests carrying ``min_confidence > 0``)
still pays a second encoder pass for the uncertainty head and a host
round-trip before its escalation verdict lands.  This kernel extends the
fused head so the whole depth-<=1 verdict comes out of a single launch:

  * loss head      gelu MLP -> softplus -> predicted losses (bb, M)
  * uncertainty    the same MLP shape over the same embedding tile ->
                   sigma (bb, M) (softplus + UNC_FLOOR, matching
                   ``core.router.uncertainty_from_emb``)
  * selection      constraint add + argmin -> first-pick expert
  * escalation     masked re-argmin of the constrained scores over the
                   experts strictly *above* the first pick in the
                   size-sorted escalation ladder -> the router-preferred
                   depth-1 escalation target

The escalation target replicates ``core.objective.cascade_choice``'s
router-preferred step exactly, including its tie-break: among
equal-scoring larger experts the one *earliest in the ladder* wins (the
host walk argmins over ``order[pos+1:]``, first occurrence first).  The
kernel reproduces that by taking the score minimum and then the minimum
ladder position among the argmin set.  When the first pick is already
the top rung, ``esc`` echoes ``choice`` (there is nowhere to go — the
host walk stops too).

Whether a request actually escalates (its confidence vs. threshold) is
resolved by the caller: thresholds are per-request scalars, cheap on the
host, and keeping them out of the kernel means one compiled program
serves every traffic mix.  Depth >= 2 escalations fall back to the
staged host walk (``serving.engine._cascade_fused``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.router import UNC_FLOOR
from repro.kernels import default_interpret
from repro.kernels.router_score.kernel import launch_plan


def _cascade_kernel(emb_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                    uw1_ref, ub1_ref, uw2_ref, ub2_ref,
                    cvals_ref, lam_ref, pos_ref,
                    pred_ref, sigma_ref, choice_ref, esc_ref):
    emb = emb_ref[...].astype(jnp.float32)               # (bb, d)
    h = jax.lax.dot_general(emb, w1_ref[...],
                            (((1,), (0,)), ((), ()))) + b1_ref[...]
    h = jax.nn.gelu(h)
    raw = jax.lax.dot_general(h, w2_ref[...],
                              (((1,), (0,)), ((), ()))) + b2_ref[...]
    pred = jax.nn.softplus(raw)                          # (bb, M)
    pred_ref[...] = pred
    # uncertainty head on the same embedding tile (sigma > 0 via the
    # softplus floor, identical math to uncertainty_from_emb)
    hu = jax.lax.dot_general(emb, uw1_ref[...],
                             (((1,), (0,)), ((), ()))) + ub1_ref[...]
    hu = jax.nn.gelu(hu)
    uraw = jax.lax.dot_general(hu, uw2_ref[...],
                               (((1,), (0,)), ((), ()))) + ub2_ref[...]
    sigma_ref[...] = jax.nn.softplus(uraw) + UNC_FLOOR   # (bb, M)
    # constrained selection: lam (bb, n_c) @ cvals (n_c, M)
    combined = pred + jax.lax.dot_general(
        lam_ref[...].astype(jnp.float32), cvals_ref[...],
        (((1,), (0,)), ((), ())))
    # (bb, 1) columns throughout: a 1-D (bb,) output block mismatches
    # XLA's tiling of the s32[Bp] result once the grid has several tiles
    choice = jnp.argmin(combined, axis=1, keepdims=True).astype(jnp.int32)
    choice_ref[...] = choice
    # depth-1 escalation: re-argmin over experts strictly later in the
    # escalation ladder than the first pick.  pos_ref holds each
    # expert's ladder position (the inverse permutation of the order).
    M = combined.shape[1]
    ids = jax.lax.broadcasted_iota(jnp.int32, combined.shape, 1)
    pos = pos_ref[...].astype(jnp.int32)[None, :]        # (1, M)
    # ladder position of each row's first pick, via one-hot contraction
    # (gathers are awkward on the TPU vector unit; M is tiny)
    pos_choice = jnp.sum(jnp.where(ids == choice, pos, 0), axis=1,
                         keepdims=True)                  # (bb, 1)
    above = pos > pos_choice                             # (bb, M)
    big = jnp.full_like(combined, jnp.inf)
    masked = jnp.where(above, combined, big)
    minval = jnp.min(masked, axis=1, keepdims=True)
    # tie-break to the earliest ladder rung among the argmin set — the
    # host walk's np.argmin over order[pos+1:] (first occurrence) exactly
    cand_pos = jnp.where(masked == minval, pos, jnp.int32(M))
    best_pos = jnp.min(cand_pos, axis=1, keepdims=True)  # (bb, 1)
    esc = jnp.sum(jnp.where(pos == best_pos, ids, 0), axis=1, keepdims=True)
    has_next = jnp.any(above, axis=1, keepdims=True)
    esc_ref[...] = jnp.where(has_next, esc, choice).astype(jnp.int32)

@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def router_score_cascade_fused(emb, w1, b1, w2, b2, uw1, ub1, uw2, ub2,
                               cvals, lam, ladder_pos, *, block_b=128,
                               interpret=None):
    """emb (B, d); loss head w1/b1/w2/b2; uncertainty head uw1/ub1/uw2/
    ub2 (same shapes); cvals (n_c, M); lam (B, n_c); ladder_pos (M,)
    int32 — each expert's position in the size-sorted escalation ladder.

    Returns ``(pred (B, M) f32, sigma (B, M) f32, choice (B,) int32,
    esc (B,) int32)`` where ``esc`` is the router-preferred depth-1
    escalation target (== ``choice`` when the pick is the top rung).
    ``interpret=None`` picks compiled on TPU/GPU, interpret on CPU.
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
    pred, sigma, choice, esc = pl.pallas_call(
        _cascade_kernel,
        grid=(plan["grid"],),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((d, hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
            pl.BlockSpec((hidden, M), lambda i: (0, 0)),
            pl.BlockSpec((M,), lambda i: (0,)),
            pl.BlockSpec((d, hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
            pl.BlockSpec((hidden, M), lambda i: (0, 0)),
            pl.BlockSpec((M,), lambda i: (0,)),
            pl.BlockSpec((n_c, M), lambda i: (0, 0)),
            pl.BlockSpec((block_b, n_c), lambda i: (i, 0)),
            pl.BlockSpec((M,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, M), jnp.float32),
            jax.ShapeDtypeStruct((Bp, M), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(emb, w1, b1, w2, b2, uw1, ub1, uw2, ub2, cvals, lam, ladder_pos)
    return pred[:B], sigma[:B], choice[:B, 0], esc[:B, 0]
