"""Single-source param builder: one builder call-site yields the init
array, the abstract ShapeDtypeStruct, *and* the logical sharding axes,
so the three never drift apart."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Builder:
    """mode: 'init' (arrays) | 'abstract' (ShapeDtypeStruct) | 'axes'."""

    def __init__(self, mode: str, key: Optional[jax.Array] = None,
                 dtype=jnp.float32):
        assert mode in ("init", "abstract", "axes")
        self.mode = mode
        self._key = key
        self._count = 0
        self.dtype = dtype

    def _next_key(self):
        self._count += 1
        return jax.random.fold_in(self._key, self._count)

    def p(self, shape: Sequence[int], axes: Tuple, *,
          init: str = "fan_in", scale: float = 1.0, fan_in: int = 0,
          dtype=None):
        """``dtype`` overrides the model's parameter dtype for this leaf
        (a leaf published in a wider type than the rest)."""
        shape = tuple(int(s) for s in shape)
        assert len(axes) == len(shape), (shape, axes)
        dt = self.dtype if dtype is None else dtype
        if self.mode == "axes":
            return tuple(axes)
        if self.mode == "abstract":
            return jax.ShapeDtypeStruct(shape, dt)
        if init == "zeros":
            return jnp.zeros(shape, dt)
        if init == "ones":
            return jnp.ones(shape, dt)
        if init == "fan_in":
            fi = fan_in or (shape[-2] if len(shape) >= 2 else shape[-1])
            std = scale / np.sqrt(max(fi, 1))
        elif init == "normal":
            std = scale
        else:
            raise ValueError(init)
        return (jax.random.normal(self._next_key(), shape, dt)
                * jnp.asarray(std, dt))


def build_all(build_fn, cfg, key=None, dtype=jnp.float32):
    """Returns (params, abstract, axes) from one structure function."""
    params = build_fn(cfg, Builder("init", key, dtype)) if key is not None else None
    abstract = build_fn(cfg, Builder("abstract", dtype=dtype))
    axes = build_fn(cfg, Builder("axes"))
    return params, abstract, axes
