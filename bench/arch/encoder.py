"""The BERT-family masked-LM encoder: the paper's experts, and the
router's encoder too (``bench/router.py``).

Layout, the one ``repro.models.model.forward`` reads: ``{"embed":
{"table"}, "units": {"l0": {...}}, "final_norm"}`` with every layer's
arrays stacked on a leading axis.  Matrices are normal with standard
deviation 1/sqrt(fan-in); LayerNorm scales are 1 + 0.1 N(0, 1) and
biases 0.1 N(0, 1), so a reference that dropped either would disagree.
Norm parameters are float32, as the program keeps them; matrices take
the encoder's ``dtype``.

Reference: token embedding; per layer, pre-LayerNorm (eps 1e-6)
multi-head attention, bidirectional, with RoPE (theta 10000,
rotate-half on the two halves of each head) on queries and keys, then a
pre-LayerNorm two-matrix MLP with tanh-GELU, each added to the
residual; a final LayerNorm.  An expert's logits are the final states
times the tied embedding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import act, layer_norm, mlm_readings, mm, rope
from bench.weights import norm_params, normal


def weights(key, e, dtype):
    L, d, H = e["num_layers"], e["d_model"], e["num_heads"]
    ff, V = e["d_ff"], e["vocab_size"]
    hd = d // H
    ks = jax.random.split(key, 10)
    lead = (L,)
    return {
        "embed": {"table": normal(ks[0], (V, d), 1 / math.sqrt(d), dtype)},
        "units": {"l0": {
            "norm1": norm_params(ks[1], lead, d),
            "mix": {"wq": normal(ks[2], (L, d, H, hd), 1 / math.sqrt(d), dtype),
                    "wk": normal(ks[3], (L, d, H, hd), 1 / math.sqrt(d), dtype),
                    "wv": normal(ks[4], (L, d, H, hd), 1 / math.sqrt(d), dtype),
                    "wo": normal(ks[5], (L, H, hd, d), 1 / math.sqrt(d), dtype)},
            "norm2": norm_params(ks[6], lead, d),
            "mlp": {"wi": normal(ks[7], (L, d, ff), 1 / math.sqrt(d), dtype),
                    "wo": normal(ks[8], (L, ff, d), 1 / math.sqrt(ff), dtype)}}},
        "final_norm": norm_params(ks[9], (), d),
    }


def model_config(name: str, e: dict, dtype: str):
    """A ``repro`` encoder config at the file's widths, the way
    ``repro.core.library._enc`` builds the repo's analogs, in the
    configuration's ``dtype``."""
    from repro.models.common import AttnConfig, ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=e["num_layers"],
        d_model=e["d_model"], num_heads=e["num_heads"],
        num_kv_heads=e["num_heads"], d_ff=e["d_ff"],
        vocab_size=e["vocab_size"],
        attn=AttnConfig(rope_theta=10000.0, causal=False),
        layer_pattern=("attn",), moe_pattern=(False,), is_encoder=True,
        tie_embeddings=True, norm_kind="layernorm", act="gelu", dtype=dtype)


def _layer(p, x, prec):
    a = act(prec)
    h = layer_norm(p["norm1"], x, prec)
    q = rope(mm("bsd,dhk->bshk", prec, h, p["mix"]["wq"]))
    k = rope(mm("bsd,dhk->bshk", prec, h, p["mix"]["wk"]))
    v = mm("bsd,dhk->bshk", prec, h, p["mix"]["wv"])
    s = mm("bshk,bthk->bhst", prec, q.astype(a), k.astype(a))
    s = s.astype(jnp.float32) / math.sqrt(q.shape[-1])
    w = jax.nn.softmax(s, axis=-1)
    o = mm("bhst,bthk->bshk", prec, w.astype(a), v)
    x = (x + mm("bshk,hkd->bsd", prec, o, p["mix"]["wo"])).astype(a)
    h = layer_norm(p["norm2"], x, prec)
    u = mm("bsd,df->bsf", prec, h, p["mlp"]["wi"])
    u = jax.nn.gelu(u.astype(jnp.float32), approximate=True).astype(a)
    return (x + mm("bsf,fd->bsd", prec, u, p["mlp"]["wo"])).astype(a)


def states(p, tokens, prec: str):
    """Final-LayerNorm hidden states (B, S, d) of an encoder."""
    x = p["embed"]["table"][tokens].astype(act(prec))
    x, _ = jax.lax.scan(lambda h, lp: (_layer(lp, h, prec), None), x,
                        p["units"]["l0"])
    return layer_norm(p["final_norm"], x, prec)


@functools.partial(jax.jit, static_argnames="prec")
def _readings(p, tokens, served, targets, mask, prec="f32"):
    return mlm_readings(states(p, tokens, prec), p["embed"]["table"],
                        served, targets, mask, prec)


def readings(p, e, tokens, served, targets, mask, prec="f32"):
    """Masked-LM readings of the tied head (``bench/arch`` says what)."""
    return _readings(p, tokens, served, targets, mask, prec=prec)


def request_flops(e: dict, seq: int) -> int:
    """FLOPs of one masked-LM expert on one request of ``seq`` tokens:
    every layer plus the tied LM head over the full vocabulary at every
    position."""
    hd = e["d_model"] // e["num_heads"]
    layers = e["num_layers"] * flops.encoder_layer_flops(
        seq, e["d_model"], e["num_heads"], hd, e["d_ff"])
    head = 2 * seq * e["d_model"] * e["vocab_size"]
    return layers + head


def param_count(e: dict) -> int:
    """Parameters of one expert from its widths: embedding, per layer
    two LayerNorms and the attention and MLP matrices, a final
    LayerNorm."""
    d, ff, V = e["d_model"], e["d_ff"], e["vocab_size"]
    per_layer = 2 * 2 * d + 4 * d * d + 2 * d * ff
    return V * d + e["num_layers"] * per_layer + 2 * d
