"""The router, the system's own model (not an expert): its weights and
its plain reference, importing nothing of the program.

Layout, the one ``repro.core.router`` reads: ``{"encoder", "head",
"unc"}``, the encoder of ``bench/arch/encoder.py`` in float32 plus two
MLPs.  The router mean-pools the encoder's final states over non-pad
ids (id 0 is pad), and its loss head and uncertainty head are each
``softplus(gelu(e W1 + b1) W2 + b2)``, the latter plus 1e-3.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.arch import encoder
from bench.reference import act, mm
from bench.weights import normal

UNC_FLOOR = 1e-3

# The router is the system's own model, one artifact per deployment: its
# weights come from this fixed seed in every run.  Drawn from the run's
# seed, they moved the share of escalated requests in ``ladder4`` from 20%
# to 79% between seeds, so the seed changed how much work a run held.
# With this seed 41% of ``ladder4``'s cascaded requests escalate (the
# share the repo's bring-up run saw) and every rung of the ladder serves.
ROUTER_SEED = 2


def _mlp_head(key, d, hidden, M):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"w1": normal(k1, (d, hidden), 1 / math.sqrt(d), jnp.float32),
            "b1": normal(k2, (hidden,), 0.1, jnp.float32),
            "w2": normal(k3, (hidden, M), 1 / math.sqrt(hidden), jnp.float32),
            "b2": normal(k4, (M,), 0.1, jnp.float32)}


def weights(key, r, M: int):
    """The router's tree for ``M`` experts from ``key`` and the
    configuration's ``router`` entry ``r``."""
    kr = jax.random.split(key, 3)
    d, hidden = r["d_model"], r["head_hidden"]
    return {"encoder": encoder.weights(kr[0], r, jnp.float32),
            "head": _mlp_head(kr[1], d, hidden, M),
            "unc": _mlp_head(kr[2], d, hidden, M)}


def _head(p, e, prec):
    h = mm("bd,dh->bh", prec, e, p["w1"]).astype(jnp.float32) + p["b1"]
    h = jax.nn.gelu(h, approximate=True).astype(act(prec))
    raw = mm("bh,hm->bm", prec, h, p["w2"]).astype(jnp.float32) + p["b2"]
    return jax.nn.softplus(raw)


@functools.partial(jax.jit, static_argnames="prec")
def readings(rp, tokens, prec="f32"):
    """Predicted losses and uncertainties (B, M), both f32."""
    h = encoder.states(rp["encoder"], tokens, prec).astype(jnp.float32)
    valid = (tokens != 0).astype(jnp.float32)[..., None]
    e = (h * valid).sum(1) / jnp.maximum(valid.sum(1), 1.0)
    e = e.astype(act(prec))
    return _head(rp["head"], e, prec), _head(rp["unc"], e, prec) + UNC_FLOOR
