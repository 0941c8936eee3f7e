"""Pallas TPU kernels for the performance-critical hot spots.

  flash_attention/  block-wise online-softmax attention (train/prefill)
  router_score/     fused Tryage routing head: scores + constraint add +
                    argmin without an HBM round-trip
  mlstm_scan/       chunkwise-parallel mLSTM recurrence (xLSTM family)

  router_cascade/   the router head plus uncertainty head, cascade
                    confidence and depth-1 escalation in one launch

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper) and ref.py (pure-jnp oracle).  ``default_interpret``
compiles them on a TPU or GPU and runs them in interpret mode on the CPU
backend, where the tests run (``JAX_PLATFORMS=cpu``); a CPU run checks
results, never speed.  On a TPU the BlockSpecs give VMEM-resident tiles
with MXU-aligned (128-multiple) matmul dims; ``chip_smoke.py`` checks
that the served path's kernels ran compiled there.
"""

from __future__ import annotations

import jax

_COMPILED_BACKENDS = ("tpu", "gpu")


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret`` argument for ``pl.pallas_call``.

    ``None`` means backend-detected: compiled where Pallas has a real
    lowering (TPU Mosaic, GPU Triton), interpret mode on the CPU, and an
    error on any other backend.  An explicit bool always wins, so tests
    can force either mode.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend in _COMPILED_BACKENDS:
        return False
    raise ValueError(f"no Pallas lowering chosen for backend {backend!r}")
