"""The system under test, built from a configuration file and a seed:
its weights, the expert library, the router and the engine, and the
warm-up of the shapes a cell's traffic uses."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import router


class _Entry(dict):
    """A configuration entry as a static argument of a jitted call."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _make(seed, router_seed, experts, r, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(experts))
    lib = [draw(k, e, dtype) for k, (draw, e) in zip(ks, experts)]
    rp = router.weights(jax.random.PRNGKey(router_seed), r, len(experts))
    return lib, rp


def make_weights(cfg: dict, seed: int, archs: list):
    """Random weights, made on the device in one jitted call in the
    types they are served in: (list of expert parameter trees from
    ``seed``, each drawn by its architecture in ``archs``, router
    parameter tree from ``router.ROUTER_SEED``).  Expert i takes the
    i-th split of the seed's key."""
    experts = tuple((a.weights, _Entry(e))
                    for a, e in zip(archs, cfg["experts"]))
    return _make(jnp.uint32(seed % 2**32), jnp.uint32(router.ROUTER_SEED),
                 experts, _Entry(cfg["router"]), jnp.dtype(cfg["dtype"]))


def library(cfg: dict, expert_params: list, archs: list):
    """The expert library, each expert served with the ``ModelConfig``
    of its architecture in ``archs``."""
    from repro.core.library import ExpertSpec, ModelLibrary
    from repro.data.corpus import DOMAINS
    from repro.models.model import count_params

    specs = []
    for e, p, a in zip(cfg["experts"], expert_params, archs):
        focus = e["focus"]
        mix = {d: (0.2 / len(DOMAINS) + (0.8 / len(focus) if d in focus
                                          else 0.0)) if focus
               else 1.0 / len(DOMAINS) for d in DOMAINS}
        specs.append(ExpertSpec(e["name"],
                                a.model_config(e["name"], e, cfg["dtype"]),
                                mix, e["recency"], source=e["source"],
                                params=p, n_params=count_params(p)))
    return ModelLibrary(specs)


def router_config(cfg: dict):
    from repro.core.router import RouterConfig
    r = cfg["router"]
    return RouterConfig(n_models=len(cfg["experts"]),
                        vocab_size=r["vocab_size"],
                        num_layers=r["num_layers"], d_model=r["d_model"],
                        num_heads=r["num_heads"], d_ff=r["d_ff"],
                        head_hidden=r["head_hidden"])


def engine(cfg: dict, lib, router_params):
    from repro.core.objective import recency_constraint, size_constraint
    from repro.serving import TryageEngine
    table = {"size": size_constraint, "recency": recency_constraint}
    e = cfg["engine"]
    return TryageEngine(
        lib, router_params, router_config(cfg),
        [table[c](lib) for c in cfg["constraints"]],
        max_batch=e["max_batch"], use_kernel=e["use_kernel"],
        buckets=e["buckets"], lane_target=e["lane_target"],
        max_wait_s=e["max_wait_s"], decision_cache=e["decision_cache"],
        fused_cascade=e["fused_cascade"],
        cascade_max_depth=e["cascade_max_depth"])


def pow2_upto(n: int) -> list[int]:
    return [1 << i for i in range(n.bit_length()) if 1 << i <= n]


def warm(eng, cfg: dict, cascade: bool) -> int:
    """Compile (or load from the persistent cache) and run once every
    program the window can launch: the decision step at each admission
    bucket 1 ... max_batch, and each expert's step at each lane bucket
    1 ... lane_target.  ``cascade``: the traffic carries confidence
    floors, so admissions take the one-launch cascade decision.
    Returns the number of programs run."""
    import jax
    import jax.numpy as jnp

    S = cfg["seq_len"]
    n_c = max(1, len(eng.constraints))
    decide = eng._decide_cascade if cascade else eng._decide
    outs = []
    for b in pow2_upto(cfg["engine"]["max_batch"]):
        outs.append(decide(eng.router_params,
                           jnp.asarray(np.zeros((b, S), np.int32)),
                           jnp.asarray(np.zeros((b, n_c), np.float32))))
    for e in eng.library.experts:
        fn = eng._expert_fns[e.name]
        for b in pow2_upto(cfg["engine"]["lane_target"]):
            z = np.zeros((b, S), np.int32)
            outs.append(fn(e.params, jnp.asarray(z), jnp.asarray(z),
                           jnp.asarray(z)))
    jax.block_until_ready(outs)
    return len(outs)
