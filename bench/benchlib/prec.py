"""Arithmetic precisions for the plain reference.

``float64`` is the reference itself.  ``default`` is its control as a
test on the CPU computes it: values kept in float32 and every matrix
product taken in one bfloat16 pass with float32 accumulation, as a
program run at JAX's ``default`` matmul precision computes on a TPU.
Every array the reference makes goes through ``arr``, every result
through ``r`` and every product through ``mm``.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

NAMES = ("float64", "default")


def _bf16(x) -> np.ndarray:
    return np.asarray(x, np.float32).astype(
        ml_dtypes.bfloat16).astype(np.float32)


class Prec:
    def __init__(self, name: str):
        if name not in NAMES:
            raise ValueError(f"unknown reference precision {name!r}")
        self.name = name

    def arr(self, x) -> np.ndarray:
        """An input or state array in this precision."""
        return self.r(x)

    def r(self, x) -> np.ndarray:
        """A computed value, rounded to this precision."""
        if self.name == "default":
            return np.asarray(x, np.float32)
        return np.asarray(x, np.float64)

    def mm(self, a, b) -> np.ndarray:
        """A matrix product."""
        if self.name == "default":
            return np.matmul(_bf16(a), _bf16(b))
        return self.r(np.matmul(a, b))


F64 = Prec("float64")
DEFAULT = Prec("default")
