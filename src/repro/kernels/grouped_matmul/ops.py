"""Grouped matmul over the experts held here: the megablox ``gmm``
Pallas kernel that ships with JAX (compiled on TPU, interpret mode
elsewhere), behind a wrapper whose name the custom call takes.

Its grid visits only the row tiles that hold a group's rows (the tile
count is read from ``sizes`` on the device), so the work follows the
entries routed here, not the worst case the static shape allows. Rows
past ``sum(sizes)`` are left unwritten by the kernel and come back as
zeros."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

from .. import on_tpu
from .ref import expert_gmm_ref

TM = 128          # row tile: the entries of one group share its tiles


def _tile(dim: int, widest: int) -> int:
    """The widest multiple of 128 up to ``widest`` that divides ``dim``;
    ``dim`` itself when it is under a lane tile or has no such divisor."""
    for t in range(widest, 127, -128):
        if dim % t == 0:
            return t
    return dim


@functools.partial(jax.jit, static_argnames=("interpret",))
def _expert_gmm(lhs, rhs, sizes, interpret):
    K, N = rhs.shape[1], rhs.shape[2]
    out = gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
              tiling=(TM, _tile(K, 2048), _tile(N, 512)),
              interpret=interpret)
    keep = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
    return jnp.where(keep[:, None], out, 0)


def expert_gmm(lhs, rhs, sizes):
    """lhs: (M, K), M a multiple of ``TM``, rows sorted by group;
    rhs: (G, K, N); sizes: (G,) int32, summing to at most M -> (M, N)
    in lhs's dtype, accumulated in float32."""
    return _expert_gmm(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                       interpret=not on_tpu())


__all__ = ["expert_gmm", "expert_gmm_ref", "TM"]
