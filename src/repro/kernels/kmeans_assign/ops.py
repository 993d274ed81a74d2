"""Public K-means assignment op (forward-only; the E-step has no grad)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.kmeans_assign.kernel import assign_fwd


def assign_with_dist(x: jax.Array, centers: jax.Array,
                     block_n: int = 256,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    interp = interpret_default() if interpret is None else interpret
    n = x.shape[0]
    bn = min(block_n, max(n, 1))
    pad = (-n) % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    a, d2 = assign_fwd(x, centers, block_n=bn, interpret=interp)
    return a[:n, 0], d2[:n, 0]


def assign(x: jax.Array, centers: jax.Array,
           interpret: Optional[bool] = None) -> jax.Array:
    return assign_with_dist(x, centers, interpret=interpret)[0]
