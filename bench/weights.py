"""Random weights for a configuration, made on the device from the seed
in one jitted call, in the types they are served in.

The layout is the one ``repro.models.model.forward`` and
``repro.core.router`` read: an encoder is ``{"embed": {"table"},
"units": {"l0": {...}}, "final_norm"}`` with every layer's arrays
stacked on a leading axis; the router adds ``head`` and ``unc`` MLPs.
Matrices are normal with standard deviation 1/sqrt(fan-in); LayerNorm
scales are 1 + 0.1 N(0, 1) and biases 0.1 N(0, 1), so a reference that
dropped either would disagree.  Norm parameters are float32, as the
program keeps them; matrices take the encoder's ``dtype``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _norm(key, lead, d):
    k1, k2 = jax.random.split(key)
    return {"scale": 1.0 + _normal(k1, lead + (d,), 0.1, jnp.float32),
            "bias": _normal(k2, lead + (d,), 0.1, jnp.float32)}


def encoder(key, L: int, d: int, H: int, ff: int, V: int, dtype):
    hd = d // H
    ks = jax.random.split(key, 10)
    lead = (L,)
    return {
        "embed": {"table": _normal(ks[0], (V, d), 1 / math.sqrt(d), dtype)},
        "units": {"l0": {
            "norm1": _norm(ks[1], lead, d),
            "mix": {"wq": _normal(ks[2], (L, d, H, hd), 1 / math.sqrt(d), dtype),
                    "wk": _normal(ks[3], (L, d, H, hd), 1 / math.sqrt(d), dtype),
                    "wv": _normal(ks[4], (L, d, H, hd), 1 / math.sqrt(d), dtype),
                    "wo": _normal(ks[5], (L, H, hd, d), 1 / math.sqrt(d), dtype)},
            "norm2": _norm(ks[6], lead, d),
            "mlp": {"wi": _normal(ks[7], (L, d, ff), 1 / math.sqrt(d), dtype),
                    "wo": _normal(ks[8], (L, ff, d), 1 / math.sqrt(ff), dtype)}}},
        "final_norm": _norm(ks[9], (), d),
    }


def _mlp_head(key, d, hidden, M):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"w1": _normal(k1, (d, hidden), 1 / math.sqrt(d), jnp.float32),
            "b1": _normal(k2, (hidden,), 0.1, jnp.float32),
            "w2": _normal(k3, (hidden, M), 1 / math.sqrt(hidden), jnp.float32),
            "b2": _normal(k4, (M,), 0.1, jnp.float32)}


def _shapes(cfg: dict):
    ex = tuple((e["num_layers"], e["d_model"], e["num_heads"], e["d_ff"],
                e["vocab_size"]) for e in cfg["experts"])
    r = cfg["router"]
    return ex, (r["num_layers"], r["d_model"], r["num_heads"], r["d_ff"],
                r["vocab_size"], r["head_hidden"])


# The router is the system's own model, one artifact per deployment: its
# weights come from this fixed seed in every run.  Drawn from the run's
# seed, they moved the share of escalated requests in ``ladder4`` from 20%
# to 79% between seeds, so the seed changed how much work a run held.
# With this seed 41% of ``ladder4``'s cascaded requests escalate (the
# share the repo's bring-up run saw) and every rung of the ladder serves.
ROUTER_SEED = 2


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _make(seed, router_seed, experts, router, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(experts))
    lib = [encoder(k, *shape, dtype) for k, shape in zip(ks, experts)]
    L, d, H, ff, V, hidden = router
    M = len(experts)
    kr = jax.random.split(jax.random.PRNGKey(router_seed), 3)
    rp = {"encoder": encoder(kr[0], L, d, H, ff, V, jnp.float32),
          "head": _mlp_head(kr[1], d, hidden, M),
          "unc": _mlp_head(kr[2], d, hidden, M)}
    return lib, rp


def make_weights(cfg: dict, seed: int):
    """(list of expert parameter trees from ``seed``, router parameter
    tree from ``ROUTER_SEED``)."""
    experts, router = _shapes(cfg)
    return _make(jnp.uint32(seed % 2**32), jnp.uint32(ROUTER_SEED),
                 experts, router, jnp.dtype(cfg["dtype"]))
