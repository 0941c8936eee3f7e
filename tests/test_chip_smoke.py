"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script refuses every platform but a TPU, and its checks that the
kernels ran compiled cannot pass in interpret mode.  These tests steer
both from here: they call its phases directly with the tiny library, so
the control flow, the serving flags and the comparison against the
plain-jnp reference engine are exercised without a chip.
"""

import importlib.util
import os

import jax
import pytest

from repro.core.router import RouterConfig, init_router
from repro.data.corpus import DomainCorpus
from repro.kernels import tiles

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_models(tiny_library):
    rc = RouterConfig(n_models=len(tiny_library), vocab_size=64,
                      num_layers=1, d_model=32, num_heads=2, d_ff=64)
    rp, _ = init_router(jax.random.PRNGKey(1), rc, uncertainty=True)
    return tiny_library, rp, rc, DomainCorpus(vocab_size=64, seed=0)


def test_refuses_a_platform_that_is_not_a_tpu(smoke, capsys):
    """Off a TPU the script exits non-zero, names the platform it found
    and prints no result line."""
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert f"found platform {jax.devices()[0].platform!r}" in err
    assert '"ok"' not in out


def test_one_chip_phases_pass_on_tiny_models(smoke, tiny_models,
                                             monkeypatch, tmp_path, capsys):
    """serve / single / cascade: every request answered, a batch over
    one kernel tile, router updates applied, and kernel choices agreeing
    with the reference outside the near-tie margin."""
    monkeypatch.setattr(smoke, "require_compiled", lambda step, *a: {})
    # no tile table: the static 128-row tile, as on a chip the table
    # does not list, so a 256-row batch spans two tiles
    monkeypatch.setenv(tiles.ENV_VAR, str(tmp_path / "no_table.json"))
    smoke.smoke_one_chip(tiny_models, requests=288, seq=32, max_batch=256)
    out = capsys.readouterr().out
    for phase in ("serve", "single", "cascade"):
        assert f"phase {phase}: " in out
    assert '"clear_disagree": 0' in out


def test_mesh_phase_passes_on_four_virtual_devices(smoke, tiny_models,
                                                   monkeypatch, tmp_path,
                                                   capsys):
    """--chips 4: expert replicas on several devices, choices and NLL
    matching the meshless engine."""
    if jax.device_count() < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=4 or more")
    monkeypatch.setenv(tiles.ENV_VAR, str(tmp_path / "no_table.json"))
    smoke.smoke_mesh(tiny_models, requests=288, seq=32, max_batch=256)
    out = capsys.readouterr().out
    assert "mesh placement: " in out and "phase mesh: " in out
