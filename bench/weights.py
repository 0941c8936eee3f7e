"""Helpers for drawing random weights, shared by the router
(``bench/router.py``) and every expert architecture
(``bench/arch/<name>.py``).  ``bench/build.py:make_weights`` draws a
configuration's weights with them on the device in one jitted call.

Matrices are normal with standard deviation 1/sqrt(fan-in); LayerNorm
scales are 1 + 0.1 N(0, 1) and biases 0.1 N(0, 1), so a reference that
dropped either would disagree.  Norm parameters are float32, as the
program keeps them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def norm_params(key, lead, d):
    """LayerNorm scale and bias of width ``d`` under leading axes
    ``lead``."""
    k1, k2 = jax.random.split(key)
    return {"scale": 1.0 + normal(k1, lead + (d,), 0.1, jnp.float32),
            "bias": normal(k2, lead + (d,), 0.1, jnp.float32)}
