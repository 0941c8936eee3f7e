"""Operations of the served path's device steps, from shapes, and the
table of chip peaks the shares are taken against.

Only matrix-unit work counts as FLOPs: the projections, the attention
score and value products, the MLP and the LM head, two FLOPs per
multiply-add.  Layer norms, softmax, the embedding gather and the
one-hot NLL contraction are elementwise or gathers and are left out,
so a share of peak computed from these counts is a model-FLOP
utilisation, not a count of everything the chip executed.
"""

from __future__ import annotations

# Published peaks per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16 per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def encoder_layer_flops(seq: int, d: int, heads: int, head_dim: int,
                        d_ff: int) -> int:
    """FLOPs of one bidirectional encoder layer over one sequence of
    ``seq`` tokens: Q/K/V and output projections, QK^T and AV, and a
    two-matrix GELU MLP."""
    hd = heads * head_dim
    proj = 2 * seq * d * 3 * hd + 2 * seq * hd * d
    attn = 2 * 2 * seq * seq * hd
    mlp = 2 * 2 * seq * d * d_ff
    return proj + attn + mlp


def expert_request_flops(e: dict, seq: int) -> int:
    """FLOPs of one masked-LM expert on one request of ``seq`` tokens:
    every layer plus the tied LM head over the full vocabulary at every
    position (``e`` is an expert entry of a configuration file)."""
    hd = e["d_model"] // e["num_heads"]
    layers = e["num_layers"] * encoder_layer_flops(
        seq, e["d_model"], e["num_heads"], hd, e["d_ff"])
    head = 2 * seq * e["d_model"] * e["vocab_size"]
    return layers + head


def router_request_flops(r: dict, seq: int) -> int:
    """FLOPs of the router encoder on one request (no LM head: the
    router mean-pools its final hidden states)."""
    hd = r["d_model"] // r["num_heads"]
    return r["num_layers"] * encoder_layer_flops(
        seq, r["d_model"], r["num_heads"], hd, r["d_ff"])


def decision_head_flops(rows: int, d: int, hidden: int, n_models: int,
                        n_constraints: int, cascade: bool) -> int:
    """FLOPs of one fused decision kernel over ``rows`` rows: the loss
    head (d -> hidden -> M), the uncertainty head of the same shape
    when ``cascade``, and the lambda-weighted constraint add."""
    heads = 2 if cascade else 1
    flops = heads * 2 * rows * (d * hidden + hidden * n_models)
    return flops + 2 * rows * n_constraints * n_models
