"""Full model assembly: embed -> scan(units) [+ remainder layers] -> norm ->
logits, with train / prefill / decode entry points and loss functions.

Sharding contract: ``init_model`` returns ``(params, logical)``; stacked
unit params carry a leading ``layers`` axis (replicated).  The scan over
units means XLA traces each hetero-unit exactly once regardless of depth —
an 80-layer 72B model lowers as fast as a 2-layer one.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import blocks
from repro.models.common import ModelConfig
from repro.models.layers import (apply_embedding, apply_norm, apply_unembed,
                                 init_embedding, init_norm)
from repro.sharding.context import shard_act


def _num_full_units(cfg: ModelConfig):
    unit = len(cfg.layer_pattern)
    return cfg.num_layers // unit, cfg.num_layers % unit


def init_model(key, cfg: ModelConfig):
    dtype = cfg.jnp_dtype
    U, rem = _num_full_units(cfg)
    k_embed, k_units, k_rem, k_head = jax.random.split(key, 4)

    params, logical = {}, {}
    params["embed"], logical["embed"] = init_embedding(
        k_embed, cfg.vocab_size, cfg.d_model, dtype)

    unit_keys = jax.random.split(k_units, U)
    params["units"] = jax.vmap(lambda k: blocks.init_unit(k, cfg, dtype)[0])(unit_keys)
    _box = {}

    def _unit_params_only(k):
        p, l = blocks.init_unit(k, cfg, dtype)
        _box["logical"] = l
        return p

    from repro.core.rngs import seeded_key  # local: core imports models

    jax.eval_shape(_unit_params_only, seeded_key(0))
    unit_logical = _box["logical"]
    logical["units"] = jax.tree.map(
        lambda ax: ("layers",) + ax, unit_logical,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))

    if rem:
        ks = jax.random.split(k_rem, rem)
        params["rem"], logical["rem"] = {}, {}
        for j in range(rem):
            kind = cfg.layer_pattern[j]
            params["rem"][f"l{j}"], logical["rem"][f"l{j}"] = blocks.init_block(
                ks[j], cfg, kind, cfg.moe_pattern[j], dtype)

    params["final_norm"], logical["final_norm"] = init_norm(
        cfg.d_model, dtype, cfg.norm_kind)
    if not cfg.tie_embeddings:
        from repro.models.layers import init_dense
        params["head"], logical["head"] = init_dense(
            k_head, cfg.d_model, cfg.vocab_size, dtype, axes=("embed", "vocab"))
    return params, logical


def init_model_logical(cfg: ModelConfig):
    """(abstract params, logical axes) without allocating anything."""
    box = {}

    def f(k):
        p, l = init_model(k, cfg)
        box["l"] = l
        return p

    from repro.core.rngs import seeded_key  # local: core imports models

    abs_params = jax.eval_shape(f, seeded_key(0))
    return abs_params, box["l"]


def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int):
    """Stacked per-unit state + remainder-layer state."""
    dtype = cfg.jnp_dtype
    U, rem = _num_full_units(cfg)
    one = blocks.init_unit_state(cfg, batch, cache_len, dtype)
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (U,) + a.shape), one)
    state = {"units": stacked}
    if rem:
        unit = len(cfg.layer_pattern)
        state["rem"] = {
            f"l{j}": blocks.init_block_state(
                cfg, cfg.layer_pattern[j], batch, cache_len, dtype,
                layer_idx=U * unit + j)
            for j in range(rem)}
    return state


def decode_state_logical(cfg: ModelConfig):
    U, rem = _num_full_units(cfg)
    one = blocks.unit_state_logical(cfg)
    is_ax = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    state = {"units": jax.tree.map(lambda ax: ("layers",) + ax, one, is_leaf=is_ax)}
    if rem:
        state["rem"] = {f"l{j}": blocks.block_state_logical(cfg.layer_pattern[j])
                        for j in range(rem)}
    return state


def _embed_in(params, cfg, batch):
    """batch: {"tokens": ids} or {"embeds": float (B,S,d)}."""
    if "embeds" in batch and batch["embeds"] is not None:
        x = batch["embeds"].astype(cfg.jnp_dtype)
    else:
        x = apply_embedding(params["embed"], batch["tokens"])
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _positions_for(cfg: ModelConfig, B, S, offset=0):
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.attn.use_mrope:
        return jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def forward(params, cfg: ModelConfig, batch, *, mode: str, state=None,
            index=None, remat=True, attn_impl="xla", positions=None,
            unit_group: int = 1, cache_capacity=None):
    """Shared forward. Returns (logits, new_state, aux)."""
    x = _embed_in(params, cfg, batch)
    x = shard_act(x, ("batch", "seq", "act_embed"))
    B, S = x.shape[0], x.shape[1]
    if positions is None:
        offset = index if mode == "decode" else 0
        positions = _positions_for(cfg, B, S, offset)
    U, rem = _num_full_units(cfg)

    def unit_body(carry, xs):
        h = carry
        unit_params, unit_state = xs
        h, new_state, aux = blocks.apply_unit(
            unit_params, h, cfg, unit_base_layer=0, mode=mode,
            positions=positions, state=unit_state, index=index,
            attn_impl=attn_impl, cache_capacity=cache_capacity)
        return h, (new_state, aux)

    body = jax.checkpoint(unit_body) if (remat and mode in ("train", "encode")) else unit_body
    states_in = state["units"] if state is not None else None
    if states_in is None:
        # dummy per-unit state for scan xs when not decoding/prefilling
        if mode == "prefill":
            states_in = init_decode_state(cfg, B, S)["units"]
        else:
            states_in = jnp.zeros((U,), jnp.float32)  # placeholder

    if mode in ("train", "encode"):
        # sqrt-depth remat: scan over groups of ``unit_group`` units, so
        # only U/unit_group residual-stream boundaries are stored for the
        # backward pass (each group is recomputed inside its VJP).
        g = unit_group if (unit_group > 1 and U % unit_group == 0) else 1

        def group_body(carry, group_params):
            h = carry
            aux_g = jnp.zeros((), jnp.float32)
            for i in range(g):
                up = jax.tree.map(lambda a: a[i], group_params)
                h, _, aux = blocks.apply_unit(
                    up, h, cfg, unit_base_layer=0, mode=mode,
                    positions=positions, state=None, index=index,
                    attn_impl=attn_impl)
                aux_g = aux_g + aux
            return h, aux_g

        gbody = jax.checkpoint(group_body) if remat else group_body
        units_g = jax.tree.map(
            lambda a: a.reshape((U // g, g) + a.shape[1:]), params["units"])
        x, auxs = jax.lax.scan(gbody, x, units_g)
        new_states = None
    else:
        x, (new_unit_states, auxs) = jax.lax.scan(
            body, x, (params["units"], states_in))
        new_states = {"units": new_unit_states}

    aux = jnp.sum(auxs)

    if rem:
        if new_states is not None:
            new_states["rem"] = {}
        for j in range(rem):
            st = state["rem"][f"l{j}"] if (state is not None and "rem" in state) else None
            if st is None and mode == "prefill":
                st = blocks.init_block_state(
                    cfg, cfg.layer_pattern[j], B, S, cfg.jnp_dtype,
                    layer_idx=U * len(cfg.layer_pattern) + j)
            x, st2, aux_j = blocks.apply_block(
                params["rem"][f"l{j}"], x, cfg, cfg.layer_pattern[j],
                cfg.moe_pattern[j], mode=mode, layer_idx=U * len(cfg.layer_pattern) + j,
                positions=positions, state=st, index=index, attn_impl=attn_impl,
                cache_capacity=cache_capacity)
            aux = aux + aux_j
            if new_states is not None:
                new_states["rem"][f"l{j}"] = st2

    x = apply_norm(params["final_norm"], x, cfg.norm_eps, cfg.norm_kind)
    if mode == "encode":
        return x, new_states, aux
    return lm_logits(params, cfg, x), new_states, aux


def lm_logits(params, cfg: ModelConfig, x):
    """Vocabulary logits (B, S, V) from final-norm hidden states."""
    if cfg.tie_embeddings:
        logits = apply_unembed(params["embed"], x)
    else:
        from repro.models.layers import apply_dense
        logits = apply_dense(params["head"], x)
    return shard_act(logits, ("batch", "seq", "vocab"))


def encode(params, cfg: ModelConfig, batch, remat=False, attn_impl="xla"):
    """Final-norm hidden states (B, S, d) — used by the Tryage router."""
    hidden, _, _ = forward(params, cfg, batch, mode="encode", remat=remat,
                           attn_impl=attn_impl)
    return hidden


# ------------------------------------------------------------- losses

def cross_entropy(logits, targets, mask):
    """Masked mean CE in f32. logits (B,S,V); targets (B,S); mask (B,S).

    The gold logit is picked with a one-hot contraction rather than a
    gather: a gather over the vocab axis forces XLA to all-gather
    model-sharded logits, while the contraction partitions cleanly (the
    one-hot is fused into the reduction and never materializes).
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.einsum("...v,...v->...", logits, onehot)
    nll = logz - gold
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lm_loss(params, cfg: ModelConfig, batch, remat=True, attn_impl="xla",
            unit_group: int = 1):
    """Causal-LM (decoder) or MLM (encoder) loss. Returns (loss, metrics)."""
    logits, _, aux = forward(params, cfg, batch, mode="train", remat=remat,
                             attn_impl=attn_impl, unit_group=unit_group)
    if cfg.is_encoder:
        targets, mask = batch["targets"], batch["mask"]
        ce = cross_entropy(logits, targets, mask)
    else:
        tokens = batch.get("targets")
        if tokens is None:
            tokens = batch["tokens"]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(tokens)
        ce = cross_entropy(logits[:, :-1], tokens[:, 1:], mask[:, 1:])
    moe_w = cfg.moe.router_aux_weight if cfg.moe else 0.0
    loss = ce + moe_w * aux
    return loss, {"ce": ce, "aux": aux}


def prefill(params, cfg: ModelConfig, batch, attn_impl="xla",
            cache_capacity=None):
    logits, state, _ = forward(params, cfg, batch, mode="prefill",
                               attn_impl=attn_impl,
                               cache_capacity=cache_capacity)
    return logits, state


def decode_step(params, cfg: ModelConfig, token_batch, state, index,
                attn_impl="xla"):
    """token_batch: {"tokens": (B,1)} (or embeds). index: scalar position."""
    logits, state, _ = forward(params, cfg, token_batch, mode="decode",
                               state=state, index=index, attn_impl=attn_impl)
    return logits[:, -1], state


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
