"""Expert architectures, one module each, found by name.

An expert entry of ``bench/configs/<config>.json`` may carry
``"arch": "<name>"``; without the key it is ``"encoder"``.  ``load``
finds ``bench/arch/<name>.py`` under the run's root first (so test data
can carry an architecture of its own), then beside this file.  A new
architecture is a new module; no file of the harness changes.

A module provides, each taking the expert's whole configuration entry
``e``:

- ``weights(key, e, dtype)``: the parameter tree drawn from ``key``, in
  the layout the program reads, matrices in ``dtype``.  It runs inside
  the one jitted call of ``bench/build.py:make_weights``, where ``e``
  is a hashable dict.
- ``model_config(name, e, dtype)``: the program's
  ``repro.models.common.ModelConfig`` that ``bench/build.py:library``
  serves the expert with.
- ``readings(p, e, tokens, served, targets, mask, prec)``: the plain
  reference's per-row readings ``(gap, nll, first)``: its best logit
  minus its logit of each ``served`` token (B, S), its masked NLL (B,),
  and the token it puts first (B, S).  ``prec`` is ``"f32"`` (the
  reference) or ``"fp8"`` (the control; see ``bench/reference.py``).
  It imports nothing of the program.  It gets each request whole:
  ``tokens`` as served (masked positions replaced), ``targets`` with
  every original id, and ``mask`` the scored positions, so a causal
  scorer can take ``targets`` as its prompt.
- ``request_flops(e, seq)``: matrix-unit FLOPs of one request of ``seq``
  tokens, counted as ``bench/flops.py`` says.
- ``param_count(e)``: the parameters the ``size`` constraint and the
  cascade's ladder compare.

The harness hands ``readings`` blocks of a fixed number of rows
(``bench/harness.py:REF_ROWS``); a module that needs smaller ones splits
its block inside ``readings``.  Helpers for writing a module:
``bench/weights.py`` and ``bench/reference.py``, which import no
architecture.
"""

from __future__ import annotations

import importlib.util
import os
import sys

ARCH_DIR = os.path.dirname(os.path.abspath(__file__))
DEFAULT = "encoder"


def load(name: str, root: str | None = None):
    """The module of architecture ``name``: ``<root>/bench/arch/<name>.py``
    where it exists, else ``bench/arch/<name>.py`` (then imported as
    ``bench.arch.<name>``, the module the router's code imports).  A
    file is loaded once per process, so its jitted functions compile
    once."""
    paths = [os.path.join(ARCH_DIR, name + ".py")]
    if root is not None:
        paths.insert(0, os.path.join(root, "bench", "arch", name + ".py"))
    path = next((p for p in paths if os.path.isfile(p)), None)
    if path is None:
        raise SystemExit(f"no architecture {name!r}: none of {paths}")
    if os.path.realpath(path) == os.path.realpath(paths[-1]):
        return importlib.import_module(f"{__name__}.{name}")
    key = "bench_arch:" + os.path.realpath(path)
    mod = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return mod


def of(cfg: dict, root: str | None = None) -> list:
    """The architecture module of each expert of ``cfg``, in order."""
    return [load(e.get("arch", DEFAULT), root) for e in cfg["experts"]]
