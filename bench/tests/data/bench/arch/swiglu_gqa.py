"""A test-only architecture: a bidirectional encoder with RMSNorm, a
SwiGLU MLP and grouped-query attention, which the program's
``forward(mode="encode")`` serves as it stands.  It shows that an
architecture other than ``encoder`` comes in as new files only.

Layout: ``bench/arch/encoder.py``'s, with norms of ``scale`` alone
(1 + 0.1 N(0, 1)), ``wk`` and ``wv`` over ``num_kv_heads`` heads that
``num_heads / num_kv_heads`` query heads each share, and the MLP's gate
``wg``.

Reference: token embedding; per layer, pre-RMSNorm (eps 1e-6)
attention, bidirectional, with RoPE (theta 10000, rotate-half) on
queries and keys, then a pre-RMSNorm MLP ``(silu(h Wi) * (h Wg)) Wo``,
each added to the residual; a final RMSNorm; logits from the tied
embedding, scored as a masked LM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import act, mlm_readings, mm, rope
from bench.weights import normal

EPS = 1e-6


def _rms_params(key, lead, d):
    return {"scale": 1.0 + normal(key, lead + (d,), 0.1, jnp.float32)}


def weights(key, e, dtype):
    L, d, H = e["num_layers"], e["d_model"], e["num_heads"]
    KV, ff, V = e["num_kv_heads"], e["d_ff"], e["vocab_size"]
    hd = d // H
    ks = jax.random.split(key, 11)
    s = 1 / math.sqrt(d)
    return {
        "embed": {"table": normal(ks[0], (V, d), s, dtype)},
        "units": {"l0": {
            "norm1": _rms_params(ks[1], (L,), d),
            "mix": {"wq": normal(ks[2], (L, d, H, hd), s, dtype),
                    "wk": normal(ks[3], (L, d, KV, hd), s, dtype),
                    "wv": normal(ks[4], (L, d, KV, hd), s, dtype),
                    "wo": normal(ks[5], (L, H, hd, d), s, dtype)},
            "norm2": _rms_params(ks[6], (L,), d),
            "mlp": {"wi": normal(ks[7], (L, d, ff), s, dtype),
                    "wg": normal(ks[8], (L, d, ff), s, dtype),
                    "wo": normal(ks[9], (L, ff, d), 1 / math.sqrt(ff),
                                 dtype)}}},
        "final_norm": _rms_params(ks[10], (), d),
    }


def model_config(name: str, e: dict, dtype: str):
    from repro.models.common import AttnConfig, ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=e["num_layers"],
        d_model=e["d_model"], num_heads=e["num_heads"],
        num_kv_heads=e["num_kv_heads"], d_ff=e["d_ff"],
        vocab_size=e["vocab_size"],
        attn=AttnConfig(rope_theta=10000.0, causal=False),
        layer_pattern=("attn",), moe_pattern=(False,), is_encoder=True,
        tie_embeddings=True, norm_kind="rmsnorm", act="silu", dtype=dtype)


def _rms(p, x, prec):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.square(xf).mean(-1, keepdims=True) + EPS)
    return (y * p["scale"]).astype(act(prec))


def _layer(p, x, prec):
    a = act(prec)
    h = _rms(p["norm1"], x, prec)
    q = rope(mm("bsd,dhk->bshk", prec, h, p["mix"]["wq"]))
    k = rope(mm("bsd,dhk->bshk", prec, h, p["mix"]["wk"]))
    v = mm("bsd,dhk->bshk", prec, h, p["mix"]["wv"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = mm("bshk,bthk->bhst", prec, q.astype(a), k.astype(a))
    s = s.astype(jnp.float32) / math.sqrt(q.shape[-1])
    w = jax.nn.softmax(s, axis=-1)
    o = mm("bhst,bthk->bshk", prec, w.astype(a), v)
    x = (x + mm("bshk,hkd->bsd", prec, o, p["mix"]["wo"])).astype(a)
    h = _rms(p["norm2"], x, prec)
    g = mm("bsd,df->bsf", prec, h, p["mlp"]["wi"]).astype(jnp.float32)
    u = mm("bsd,df->bsf", prec, h, p["mlp"]["wg"]).astype(jnp.float32)
    z = (jax.nn.silu(g) * u).astype(a)
    return (x + mm("bsf,fd->bsd", prec, z, p["mlp"]["wo"])).astype(a)


def states(p, tokens, prec: str):
    x = p["embed"]["table"][tokens].astype(act(prec))
    x, _ = jax.lax.scan(lambda h, lp: (_layer(lp, h, prec), None), x,
                        p["units"]["l0"])
    return _rms(p["final_norm"], x, prec)


@functools.partial(jax.jit, static_argnames="prec")
def _readings(p, tokens, served, targets, mask, prec="f32"):
    return mlm_readings(states(p, tokens, prec), p["embed"]["table"],
                        served, targets, mask, prec)


def readings(p, e, tokens, served, targets, mask, prec="f32"):
    return _readings(p, tokens, served, targets, mask, prec=prec)


def request_flops(e: dict, seq: int) -> int:
    d, H, KV = e["d_model"], e["num_heads"], e["num_kv_heads"]
    hd = d // H
    proj = 2 * seq * d * (2 * H + 2 * KV) * hd
    attn = 2 * 2 * seq * seq * H * hd
    mlp = 3 * 2 * seq * d * e["d_ff"]
    head = 2 * seq * d * e["vocab_size"]
    return e["num_layers"] * (proj + attn + mlp) + head


def param_count(e: dict) -> int:
    d, H, KV = e["d_model"], e["num_heads"], e["num_kv_heads"]
    hd = d // H
    per_layer = 2 * d + (2 * H + 2 * KV) * d * hd + 3 * d * e["d_ff"]
    return e["vocab_size"] * d + e["num_layers"] * per_layer + d
