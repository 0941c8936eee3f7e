"""Helpers of the plain references for the output check, importing
nothing of the program: the matrix products in each precision, the
norms, RoPE and the tied masked-LM head that the router's reference
(``bench/router.py``) and every expert architecture's
(``bench/arch/<name>.py``) are written with; the objective's verdict
and constraint costs; and the blocked application of a reference.

Precision.  ``"f32"`` is the reference: float32 weights (bf16 weights
widened exactly), float32 activations and every matrix product at
``Precision.HIGHEST``.  The lower precision is the control that stands
in for the program to show the check can fail:

- ``"fp8"``: both operands of every matrix product quantised to
  float8_e4m3fn with one scale per tensor (absolute maximum to 448),
  activations stored in bfloat16 (the control of the experts, which the
  configuration states in bfloat16, and of the router, whose float32
  products run at the TPU's default precision as one bfloat16 pass).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
LN_EPS = 1e-6


def _q8(x):
    """Per-tensor scaled float8_e4m3fn round trip (values stay f32)."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec: str, prec: str, a, b):
    """einsum under the given precision; result in the activation type."""
    if prec == "f32":
        return jnp.einsum(spec, a.astype(jnp.float32),
                          b.astype(jnp.float32), precision=HI)
    if prec == "fp8":
        a, b = _q8(a), _q8(b)
    out = jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16)


def act(prec: str):
    """The activation type of ``prec``."""
    return jnp.float32 if prec == "f32" else jnp.bfloat16


def layer_norm(p, x, prec):
    """LayerNorm (eps 1e-6) with scale and bias, in ``act(prec)``."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mu).mean(-1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]
    return y.astype(act(prec))


def rope(x, theta=10000.0):
    """x (B, S, H, D): rotate the halves (x1, x2) of each head by the
    angle position * theta^(-2i/D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mlm_readings(h, table, served, targets, mask, prec):
    """Per row, from final states ``h`` (B, S, d) and the tied
    embedding ``table``: the best logit minus the logit of each served
    token (B, S), the masked NLL (B,), and the token put first (B, S).
    ``served`` are the tokens whose gap is read."""
    logits = mm("bsd,vd->bsv", prec, h, table)
    logits = logits.astype(jnp.float32)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    nll = jax.nn.logsumexp(logits, -1) - gold
    m = mask.astype(jnp.float32)
    return (best - got, (nll * m).sum(-1) / jnp.maximum(m.sum(-1), 1.0),
            logits.argmax(-1).astype(jnp.int32))


def in_blocks(fn, rows: int, *arrays):
    """Apply ``fn`` to ``arrays`` in blocks of ``rows`` rows (the last
    block padded by repeating row 0), concatenating numpy results."""
    n = len(arrays[0])
    outs = []
    for i in range(0, n, rows):
        blk = [a[i:i + rows] for a in arrays]
        pad = rows - len(blk[0])
        if pad:
            blk = [np.concatenate([b, np.repeat(b[:1], pad, 0)])
                   for b in blk]
        res = fn(*[jnp.asarray(b) for b in blk])
        res = res if isinstance(res, tuple) else (res,)
        outs.append([np.asarray(r)[:rows - pad] for r in res])
    return tuple(np.concatenate(col) for col in zip(*outs))


# ------------------------------------------------------------ routing

def param_counts(cfg: dict, archs: list) -> list[int]:
    """Each expert's parameters, as its architecture counts them."""
    return [a.param_count(e) for a, e in zip(archs, cfg["experts"])]


def constraint_costs(cfg: dict, archs: list) -> np.ndarray:
    """(n_c, M) costs of the configuration's constraints: ``size`` is
    parameters over the largest expert's, ``recency`` is 1 - recency."""
    sizes = np.array(param_counts(cfg, archs), float)
    table = {"size": sizes / sizes.max(),
             "recency": 1.0 - np.array([e["recency"]
                                        for e in cfg["experts"]], float)}
    return np.stack([table[c] for c in cfg["constraints"]])


def ladder(cfg: dict, archs: list) -> list[int]:
    """Experts by ascending parameter count, ties in library order."""
    sizes = param_counts(cfg, archs)
    return sorted(range(len(sizes)), key=lambda i: (sizes[i], i))


def _argmin_margin(s: np.ndarray) -> tuple[int, float]:
    i = int(np.argmin(s))
    rest = np.delete(s, i)
    return i, (float(rest.min() - s[i]) if len(rest) else math.inf)


def verdict(scores, conf, threshold: float, order: list[int],
            max_depth: int) -> tuple[int, float]:
    """The objective's verdict for one request and the smallest margin
    on its way: ``scores`` (M,) are predicted losses plus the weighted
    constraint costs, ``conf`` (M,) the confidences 1 / (1 + sigma).
    First pick: the lowest score.  While the current expert's
    confidence is under ``threshold`` (when it is above 0), fewer than
    ``max_depth`` steps were taken and a larger expert exists, step to
    the lowest-scoring strictly larger expert.  The margin is the least
    distance of any test on the way from flipping: the gap from the
    best score to the second best among the candidates, or from a
    confidence to the threshold."""
    s = np.asarray(scores, np.float64)
    cur, margin = _argmin_margin(s)
    if threshold <= 0.0 or max_depth <= 0:
        return cur, margin
    pos, depth = order.index(cur), 0
    while pos + 1 < len(order) and depth < max_depth:
        margin = min(margin, abs(float(conf[order[pos]]) - threshold))
        if conf[order[pos]] >= threshold:
            break
        rest = order[pos + 1:]
        j, m = _argmin_margin(s[rest])
        margin = min(margin, m)
        pos += 1 + j
        depth += 1
    return order[pos], margin
