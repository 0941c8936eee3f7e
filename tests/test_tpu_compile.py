"""Compiles for a described TPU v5e, without the chip.

The TPU compiler is installed with jax, and it compiles for a chip that
is described and not attached: these tests lower the served path's
kernels and jitted steps at the widths the repo serves and compile them
for one chip of a ``v5e:2x2`` topology.  Nothing runs; a compile that
passes says the chip's compiler accepts the program (block shapes,
layouts, scoped VMEM), which interpret mode on the CPU cannot.

The topology is described inside a module-scoped fixture, never at
import, so every test worker collects the same tests and only the
worker given this file loads the TPU library.  It skips where no
topology can be described.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.library import paper_library_specs
from repro.core.router import RouterConfig
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mlstm_scan.kernel import mlstm_chunkwise_bh
from repro.kernels.router_cascade.kernel import router_score_cascade_fused
from repro.kernels.router_score.kernel import router_score_fused
from repro.models.model import init_model
from repro.serving.engine import TryageEngine

RC = RouterConfig(n_models=11)        # the served router: d=128, hidden=128
N_CONSTRAINTS = 2                     # size + recency


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Lower and compile for the described chip; return the HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("batch", [32, 256, 1024])
def test_router_score_compiles(one_chip, batch):
    """The fused decision kernel at the served router width, at batches
    of one tile, two tiles and eight tiles (the 1-D choice output used
    to be refused by Mosaic once the grid had several tiles)."""
    d, h, M = RC.d_model, RC.head_hidden, RC.n_models
    s = functools.partial(_spec, one_chip)
    hlo = _compile(functools.partial(router_score_fused, interpret=False),
                   s((batch, d)), s((d, h)), s((h,)), s((h, M)), s((M,)),
                   s((N_CONSTRAINTS, M)), s((batch, N_CONSTRAINTS)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch", [32, 256, 1024])
def test_router_cascade_compiles(one_chip, batch):
    """The one-launch cascade kernel at the served width and batches."""
    d, h, M = RC.d_model, RC.head_hidden, RC.n_models
    s = functools.partial(_spec, one_chip)
    head = (s((d, h)), s((h,)), s((h, M)), s((M,)))
    hlo = _compile(
        functools.partial(router_score_cascade_fused, interpret=False),
        s((batch, d)), *head, *head, s((N_CONSTRAINTS, M)),
        s((batch, N_CONSTRAINTS)), s((M,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_largest_expert_execute_step_compiles(one_chip):
    """The Execute stage's jitted step for the library's largest expert
    (6 x 256, vocabulary 512) at bucket 32 x 128 tokens."""
    spec = max(paper_library_specs(),
               key=lambda e: e.cfg.num_layers * e.cfg.d_model)
    cfg = spec.cfg
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                               cfg)[0])
    params = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                          params)
    tok = _spec(one_chip, (32, 128), jnp.int32)
    step = functools.partial(TryageEngine._expert_forward, cfg=cfg)
    compiled = jax.jit(step).lower(params, tok, tok, tok).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_flash_attention_compiles(one_chip):
    """Flash attention at qwen1.5-0.5b's width: 16 heads of 64, a
    4096-token causal sequence, bfloat16."""
    x = _spec(one_chip, (1, 4096, 16, 64), jnp.bfloat16)
    hlo = _compile(functools.partial(flash_attention, interpret=False),
                   x, x, x)
    assert "tpu_custom_call" in hlo


def test_mlstm_scan_compiles(one_chip):
    """The chunkwise mLSTM scan at head dim 256 over 2048 steps.
    xlstm-1.3b's head dim of 1024 does not fit the kernel's
    whole-sequence blocks in scoped VMEM; that is an open item."""
    BH, S, dh = 8, 2048, 256
    s = functools.partial(_spec, one_chip)
    hlo = _compile(functools.partial(mlstm_chunkwise_bh, interpret=False),
                   s((BH, S, dh)), s((BH, S, dh)), s((BH, S, dh)),
                   s((BH, S)), s((BH, S)), s((BH, dh, dh)), s((BH, dh)),
                   s((BH,)))
    assert "tpu_custom_call" in hlo
