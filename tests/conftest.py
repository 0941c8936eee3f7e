"""Shared fixtures.  The tests run on the CPU backend
(``JAX_PLATFORMS=cpu``), where the Pallas kernels run in interpret mode;
``test_tpu_compile.py`` compiles for a described TPU without one.  Tests
see one CPU device unless ``XLA_FLAGS=--xla_force_host_platform_device_
count=N`` is set for the multi-device tests; the 512-device flag belongs
only to ``launch/dryrun.py`` subprocesses."""

import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def corpus():
    from repro.data.corpus import DomainCorpus
    return DomainCorpus(vocab_size=512, seed=0)


@pytest.fixture(scope="session")
def tiny_library():
    """3 untrained tiny experts (routing still well-defined) — the shared
    library for serving/scheduler tests."""
    from repro.core.library import ExpertSpec, ModelLibrary, _enc
    from repro.models.model import count_params, init_model
    lib = ModelLibrary([
        ExpertSpec("small", _enc("small", 1, 32, 2, 64, 64), {}, 0.5),
        ExpertSpec("mid", _enc("mid", 1, 48, 2, 96, 64), {}, 0.5),
        ExpertSpec("big", _enc("big", 2, 64, 2, 128, 64), {}, 0.9),
    ])
    for i, e in enumerate(lib.experts):
        e.params, _ = init_model(jax.random.PRNGKey(i), e.cfg)
        e.n_params = count_params(e.params)
    return lib
