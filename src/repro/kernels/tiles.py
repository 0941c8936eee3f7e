"""Autotuned tile-table consultation for the Pallas kernels.

``launch/autotune.py`` sweeps tile candidates per (backend, kernel,
batch) and persists the winners to ``experiments/tryage/tile_table.json``
(override with the ``REPRO_TILE_TABLE`` env var or ``set_table_path``,
e.g. from ``launch/serve.py --tile-table``).  The kernel ops wrappers
call ``tile_for`` when the caller left the tile argument at ``None``:
a missing/unreadable table, an unknown kernel, or a backend the table
does not list all fall back to the static default, and a caller who
passes an explicit tile is never second-guessed.

Table schema (see ``launch.autotune.write_table``)::

    {"version": 1,
     "<backend>": {"<kernel>": {"<batch>": {"block_b": 256,
                                            "effective_block_b": 256,
                                            ...timings...}}}}

Lookup picks the largest tabulated batch <= the requested batch (the
tile that won at 4k is the best prior for 5k), else the smallest entry.
"""

from __future__ import annotations

import json
import os
import threading

DEFAULT_PATH = os.path.join("experiments", "tryage", "tile_table.json")
ENV_VAR = "REPRO_TILE_TABLE"

_lock = threading.Lock()
_override_path: str | None = None
# (path, mtime) -> parsed table; None caches a failed load so a missing
# table costs one stat per call, not a re-parse attempt
_cache: dict = {}


def set_table_path(path: str | None) -> None:
    """Process-wide table override (``--tile-table``); ``None`` restores
    the env-var/default resolution."""
    global _override_path
    with _lock:
        _override_path = path
        _cache.clear()


def table_path() -> str:
    if _override_path is not None:
        return _override_path
    return os.environ.get(ENV_VAR, DEFAULT_PATH)


def load_table(path: str | None = None) -> dict | None:
    """The parsed tile table, or None when absent/unreadable.  Cached on
    (path, mtime) so serving-path consults cost one ``os.stat``."""
    path = path or table_path()
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    key = (path, mtime)
    with _lock:
        if key in _cache:
            return _cache[key]
    try:
        with open(path) as f:
            table = json.load(f)
        if not isinstance(table, dict):
            table = None
    except (OSError, ValueError):
        table = None
    with _lock:
        _cache.clear()
        _cache[key] = table
    return table


def _backend() -> str:
    import jax
    return jax.default_backend()


def tile_for(kernel: str, batch: int, param: str, default: int,
             backend: str | None = None, path: str | None = None) -> int:
    """The tuned value of ``param`` for ``kernel`` at ``batch`` on this
    backend, or ``default`` when the table has nothing to say."""
    table = load_table(path)
    if table is None:
        return default
    entries = table.get(backend or _backend(), {}).get(kernel)
    if not isinstance(entries, dict) or not entries:
        return default
    batches = sorted(int(b) for b in entries if str(b).isdigit())
    if not batches:
        return default
    at_most = [b for b in batches if b <= int(batch)]
    pick = at_most[-1] if at_most else batches[0]
    entry = entries[str(pick)]
    val = entry.get(param) if isinstance(entry, dict) else None
    return int(val) if isinstance(val, (int, float)) else default
