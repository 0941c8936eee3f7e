"""Roofline-driven tile autotuner (``launch.autotune``) and the tile
table the kernels consult (``kernels.tiles``).

Modeled-only mode (``measure=False``) is deterministic, so the schema
and effective-tile honesty checks run it for real; wall-timing is
exercised on a single tiny candidate.  Table consultation is tested
against synthetic tables via the explicit ``path=`` argument so the
process-wide override/cache state is never touched.
"""

import json

import jax
import numpy as np
import pytest

from repro.kernels import tiles
from repro.kernels.router_score.kernel import launch_plan
from repro.launch import autotune as at
from repro.launch.roofline import (PRESETS, Roofline, preset_for,
                                   resolve_preset)


# ------------------------------------------------------------ roofline

def test_presets_and_resolution():
    assert set(PRESETS) == {"tpu-v5e", "gpu", "cpu"}
    assert resolve_preset("gpu") is PRESETS["gpu"]
    # auto-detection lands on a real preset for the live backend
    assert resolve_preset("auto") in PRESETS.values()
    assert resolve_preset(None) in PRESETS.values()
    with pytest.raises(KeyError):
        resolve_preset("h100-from-the-future")


@pytest.mark.parametrize("backend,kind,want", [
    ("tpu", "TPU v5 lite", "tpu-v5e"),
    ("tpu", "TPU v4", None),
    ("cuda", "NVIDIA A100", "gpu"),
    ("cpu", "cpu", "cpu"),
    ("metal", "Apple M2", None),
])
def test_preset_for_keys_tpus_by_device_kind(backend, kind, want):
    """A TPU's peaks come from its device kind; a kind or backend with
    no preset raises instead of borrowing another chip's peaks."""
    if want is None:
        with pytest.raises(ValueError):
            preset_for(backend, kind)
    else:
        assert preset_for(backend, kind) is PRESETS[want]


def test_roofline_uses_preset_ceilings():
    rl = Roofline(flops=1e12, hbm_bytes=1e9, collective_bytes=0.0,
                  hw=PRESETS["cpu"])
    assert rl.t_compute == pytest.approx(1.0)          # 1e12 / 1e12
    assert rl.t_memory == pytest.approx(1e9 / 100e9)
    assert rl.dominant == "compute" and rl.t_bound == pytest.approx(1.0)
    assert rl.as_dict()["hw"] == "cpu"
    # same totals under a faster preset: bound shrinks
    fast = Roofline(flops=1e12, hbm_bytes=1e9, collective_bytes=0.0,
                    hw=PRESETS["gpu"])
    assert fast.t_bound < rl.t_bound


# ------------------------------------------------------------ candidates

def test_router_candidates_effective_tiles_are_honest():
    """Every candidate's recorded effective tile equals the kernel's own
    launch-plan clamp, and clamped duplicates are deduped."""
    cands = at._router_candidates(96, np.random.default_rng(0))
    assert cands
    effs = [c.record["effective_block_b"] for c in cands]
    assert len(set(effs)) == len(effs)                  # deduped
    for c in cands:
        plan = launch_plan(96, c.params["block_b"])
        assert c.record["effective_block_b"] == plan["block_b"]
        assert c.record["grid"] == plan["grid"]
        assert c.record["effective_block_b"] <= 96


def test_measure_candidate_times_a_real_run():
    cands = at._router_candidates(32, np.random.default_rng(1))
    t = at.measure_candidate(cands[0], repeats=2)
    assert np.isfinite(t) and t > 0.0


# ------------------------------------------------------- tune + persist

@pytest.fixture(scope="module")
def modeled_table():
    """One deterministic modeled-only sweep of the router kernel."""
    return at.autotune(kernels=["router_score"], batches=(64,),
                       preset="cpu", measure=False)


def test_tune_kernel_modeled_schema(modeled_table):
    backend = jax.default_backend()
    assert modeled_table["version"] == 1
    entries = modeled_table[backend]["router_score"]
    assert set(entries) == {"64"}
    e = entries["64"]
    assert set(e) >= {"block_b", "effective_block_b", "grid",
                      "modeled_s", "measured_s"}
    assert e["modeled_s"] > 0.0
    assert e["measured_s"] is None                      # --no-measure
    assert e["effective_block_b"] == launch_plan(64, e["block_b"])["block_b"]
    # deterministic: a second identical sweep reproduces the table
    again = at.autotune(kernels=["router_score"], batches=(64,),
                        preset="cpu", measure=False)
    assert again == modeled_table


def test_write_and_merge_table(tmp_path, modeled_table):
    backend = jax.default_backend()
    path = str(tmp_path / "table.json")
    # pre-existing entries for a foreign backend and another kernel
    old = {"version": 1,
           "tpu": {"router_score": {"1000": {"block_b": 512}}},
           backend: {"flash_attention": {"8": {"block_q": 64}}}}
    at.write_table(old, path)
    merged = at.merge_table(modeled_table, path)
    at.write_table(merged, path)
    out = json.loads(open(path).read())
    assert out["tpu"]["router_score"]["1000"]["block_b"] == 512
    assert out[backend]["flash_attention"]["8"]["block_q"] == 64
    assert out[backend]["router_score"]["64"]["block_b"] \
        == modeled_table[backend]["router_score"]["64"]["block_b"]
    # merge over a missing/corrupt file degrades to the new table
    assert at.merge_table(modeled_table, str(tmp_path / "nope.json")) \
        == modeled_table
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert at.merge_table(modeled_table, str(bad)) == modeled_table


def test_kernels_consult_written_table(tmp_path, modeled_table):
    """End to end: a tuned table written to disk changes what the ops
    wrapper's tile consult returns."""
    backend = jax.default_backend()
    tuned = modeled_table[backend]["router_score"]["64"]["block_b"]
    path = str(tmp_path / "table.json")
    at.write_table(modeled_table, path)
    assert tiles.tile_for("router_score", 64, "block_b", 128,
                          path=path) == tuned
    # untabulated kernel falls back to the caller's default
    assert tiles.tile_for("router_cascade", 64, "block_b", 128,
                          path=path) == 128


# ------------------------------------------------------- tile_for rules

def _table(tmp_path, table):
    p = tmp_path / "t.json"
    p.write_text(json.dumps(table))
    return str(p)


def test_tile_for_batch_selection(tmp_path):
    path = _table(tmp_path, {
        "version": 1,
        "cpu": {"k": {"100": {"p": 32}, "400": {"p": 64}}}})
    # largest tabulated batch <= requested
    assert tiles.tile_for("k", 100, "p", 8, backend="cpu", path=path) == 32
    assert tiles.tile_for("k", 250, "p", 8, backend="cpu", path=path) == 32
    assert tiles.tile_for("k", 4000, "p", 8, backend="cpu", path=path) == 64
    # below the smallest entry: smallest entry is the best prior
    assert tiles.tile_for("k", 10, "p", 8, backend="cpu", path=path) == 32
    # unknown param / kernel / backend: default
    assert tiles.tile_for("k", 100, "q", 8, backend="cpu", path=path) == 8
    assert tiles.tile_for("nope", 100, "p", 8, backend="cpu",
                          path=path) == 8
    assert tiles.tile_for("k", 100, "p", 8, backend="tpu", path=path) == 8


def test_tile_for_never_raises(tmp_path):
    # missing file
    assert tiles.tile_for("k", 10, "p", 7,
                          path=str(tmp_path / "missing.json")) == 7
    # corrupt json
    bad = tmp_path / "bad.json"
    bad.write_text("[[[")
    assert tiles.tile_for("k", 10, "p", 7, path=str(bad)) == 7
    # wrong shapes inside an otherwise-valid file
    weird = _table(tmp_path, {"cpu": {"k": {"x": {"p": 1}, "8": 3}}})
    assert tiles.tile_for("k", 10, "p", 7, backend="cpu",
                          path=weird) == 7


def test_checked_in_table_is_valid():
    """The repo's own tile table parses and its router entries honour
    the effective-tile contract."""
    table = tiles.load_table(tiles.DEFAULT_PATH)
    assert table is not None and table.get("version") == 1
    for backend, kernels in table.items():
        if backend == "version":
            continue
        for b, e in kernels.get("router_score", {}).items():
            plan = launch_plan(int(b), e["block_b"])
            assert e["effective_block_b"] == plan["block_b"]
            assert e["grid"] == plan["grid"]
