"""How the reference rounds: the dtype state is stored in, and the dtype
matrix-multiplication operands are rounded to before a float32 product.

The reference proper stores parameters and optimizer state in the
configuration's parameter dtype and computes everything else in float32 at
``Precision.HIGHEST``.  The control stores parameters in float8 (e4m3) and
rounds every matmul operand to it: the next precision below bfloat16.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Numerics:
    param_store: Any = jnp.bfloat16  # parameters as stored between steps
    state_store: Any = jnp.bfloat16  # Adam moments, residuals, updates
    operand: Optional[Any] = None  # matmul operands rounded to this

    def rnd(self, x: jax.Array) -> jax.Array:
        """A matmul operand as this numerics sees it, in float32."""
        if self.operand is None:
            return x.astype(jnp.float32)
        return x.astype(self.operand).astype(jnp.float32)

    def mm(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.matmul(self.rnd(a), self.rnd(b), precision=HIGHEST)

    def einsum(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.einsum(spec, self.rnd(a), self.rnd(b), precision=HIGHEST)


def of(param_dtype: str) -> Numerics:
    """The reference for a configuration stored in ``param_dtype``."""
    dt = jnp.dtype(param_dtype)
    return Numerics(param_store=dt, state_store=dt)


def control(param_dtype: str) -> Numerics:
    """The control: one precision step below ``param_dtype`` (bfloat16)."""
    if jnp.dtype(param_dtype) != jnp.bfloat16:
        raise ValueError(f"no control defined below {param_dtype}")
    return Numerics(param_store=jnp.float8_e4m3fn, state_store=jnp.bfloat16,
                    operand=jnp.float8_e4m3fn)
