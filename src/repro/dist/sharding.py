"""Logical-axis sharding rules (paper §4.3; TF-Replicator-style).

Model code never names mesh axes. It annotates values with *logical*
axes (``BATCH``, ``MLP``, ``VOCAB``, ...); this module resolves those
to concrete mesh axes once per (config, mesh) pair and hands back
``ShardingRules``. The indirection is what lets the same model run on a
1-device CPU, a (data, model) pod slice, and a (pod, data, model)
multi-pod mesh without touching a single layer definition — the
paper's "partitioning a computation across devices" as a pure naming
layer.

Resolution is divisibility-aware: a logical axis is only bound to a
mesh axis when every tensor dimension carrying it divides the axis
size (e.g. 60 experts do NOT shard 16-way; their hidden width does
instead). ``constrain`` additionally re-checks the actual operand
shape at trace time and silently drops non-dividing axes, so sharding
annotations are always safe to leave in the code — off-mesh they are
no-ops.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "BATCH", "SEQ", "ATTN_SEQ", "ACT_SEQ", "EMBED", "MLP", "HEAD", "HEADS",
    "KV_HEADS", "HEAD_DIM", "VOCAB", "EXPERT", "EXPERT_MLP", "INNER",
    "STATE", "LAYERS", "CACHE_KV", "CACHE_HD", "STAGE", "SLOT", "BLOCK",
    "ShardingRules", "resolve_rules", "constrain", "logical_to_sharding",
    "carve_slices", "slice_mesh", "transfer_sharding",
]

# --------------------------- logical axes -----------------------------------
# Plain strings: specs read naturally, serialize in checkpoint manifests,
# and compare by value across module reloads.

BATCH = "batch"          # global batch (data parallel)
SEQ = "seq"              # generic sequence axis
ATTN_SEQ = "attn_seq"    # sequence inside attention (context parallel)
ACT_SEQ = "act_seq"      # inter-block residual stream (sequence parallel)
EMBED = "embed"          # d_model; kept replicated (residual stream)
MLP = "mlp"              # feed-forward hidden (tensor parallel)
HEADS = "heads"          # query heads (tensor parallel)
HEAD = HEADS             # alias
KV_HEADS = "kv_heads"    # key/value heads (GQA may not divide)
HEAD_DIM = "head_dim"
VOCAB = "vocab"          # (padded) vocabulary
EXPERT = "expert"        # MoE expert pool
EXPERT_MLP = "expert_mlp"  # per-expert hidden (when EXPERT can't shard)
INNER = "inner"          # SSM d_inner
STATE = "state"          # SSM state dim
LAYERS = "layers"        # stacked-layer leading dim (never sharded)
CACHE_KV = "cache_kv"    # KV-cache head axis
CACHE_HD = "cache_hd"    # KV-cache head_dim axis
STAGE = "stage"          # pipeline stage (repro.dist.pipeline)
SLOT = "slot"            # serve decode-slot pool (repro.serve.scheduler):
                         # the cache batch axis of a slot pool — data-
                         # parallel like BATCH, but named separately so
                         # slot-pool placement reads as what it is
BLOCK = "kv_block"       # paged KV-cache physical-block axis
                         # (repro.serve.kv_cache.PagedKVCache): block
                         # pools spread over the data axes, the paged
                         # analogue of sharding dense columns over SLOT

# Mesh axes batch-like logical axes map onto, outermost first.
_DATA_AXES = ("pod", "data")
_MODEL_AXIS = "model"
_STAGE_AXIS = "stage"

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Immutable logical-axis -> mesh-axes table, bound to a mesh."""

    mesh: Optional[Mesh]
    table: Dict[str, MeshAxes]

    def mesh_axes(self, logical: Optional[str]) -> MeshAxes:
        """Mesh axis (or axes tuple) a logical axis resolves to, or None."""
        if logical is None:
            return None
        return self.table.get(logical)

    def axis_size(self, logical: Optional[str]) -> int:
        """Number of shards the logical axis is split into (1 if unsharded)."""
        ax = self.mesh_axes(logical)
        if ax is None or self.mesh is None:
            return 1
        axes = (ax,) if isinstance(ax, str) else ax
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    def spec(self, logical_spec, dims=None) -> P:
        """PartitionSpec for a tuple of logical axes (None = replicated).

        A mesh axis may appear at most once per spec; later duplicates
        are dropped. With ``dims`` (the operand shape), axes whose
        shard count does not divide the dimension are dropped too.
        """
        used = set()
        out = []
        for i, logical in enumerate(logical_spec):
            ax = self.mesh_axes(logical)
            if ax is None or self.mesh is None:
                out.append(None)
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            axes = tuple(a for a in axes if a not in used
                         and a in self.mesh.shape)
            if dims is not None:
                n = 1
                for a in axes:
                    n *= self.mesh.shape[a]
                if n == 0 or dims[i] % n != 0:
                    out.append(None)
                    continue
            if not axes:
                out.append(None)
                continue
            used.update(axes)
            out.append(axes[0] if len(axes) == 1 else axes)
        return P(*out)

    def sharding(self, logical_spec, mesh: Optional[Mesh] = None,
                 dims=None) -> NamedSharding:
        """NamedSharding for a logical spec (``()`` = fully replicated)."""
        use = mesh if mesh is not None else self.mesh
        if use is None:
            raise ValueError("ShardingRules has no mesh; pass one explicitly")
        return NamedSharding(use, self.spec(logical_spec, dims=dims))


def _present(mesh: Mesh, axis: str) -> bool:
    return axis in mesh.shape and mesh.shape[axis] > 1


def _divides(dim: int, size: int) -> bool:
    return dim > 0 and size > 0 and dim % size == 0


def resolve_rules(mesh: Optional[Mesh], *, d_model: int = 0, n_heads: int = 0,
                  n_kv_heads: int = 0, head_dim: int = 0, d_ff: int = 0,
                  vocab: int = 0, n_experts: int = 0,
                  d_inner: int = 0) -> ShardingRules:
    """Bind logical axes to the mesh for one model's dimensions.

    - ``BATCH`` spreads over every data-like axis present ("pod", "data").
    - Tensor-parallel axes (``MLP``/``VOCAB``/``HEADS``/``INNER``/...)
      bind to "model" only when the corresponding dimension divides the
      axis size, so resolution never produces invalid parameter shards.
    - ``EXPERT`` and ``EXPERT_MLP`` are mutually exclusive on "model"
      (they co-occur in one weight spec): experts shard when the pool
      divides, otherwise the per-expert hidden width does.
    - ``ATTN_SEQ``/``ACT_SEQ`` reuse "model" for sequence/context
      parallelism of activations (checked against shapes at constrain
      time, not here).
    - ``STAGE`` binds to a "stage" axis when the mesh has one
      (``repro.dist.pipeline``).
    """
    table: Dict[str, MeshAxes] = {a: None for a in (
        BATCH, SEQ, ATTN_SEQ, ACT_SEQ, EMBED, MLP, HEADS, KV_HEADS,
        HEAD_DIM, VOCAB, EXPERT, EXPERT_MLP, INNER, STATE, LAYERS,
        CACHE_KV, CACHE_HD, STAGE, SLOT, BLOCK)}
    if mesh is None:
        return ShardingRules(mesh=None, table=table)

    data = tuple(a for a in _DATA_AXES if _present(mesh, a))
    if data:
        table[BATCH] = data if len(data) > 1 else data[0]
        # Serve slot pools are a batch: slots spread over the same
        # data axes (divisibility re-checked per shape at spec time).
        # Paged KV block pools likewise spread their physical-block
        # axis over the data axes (repro.serve.kv_cache).
        table[SLOT] = table[BATCH]
        table[BLOCK] = table[BATCH]
    if _present(mesh, _STAGE_AXIS):
        table[STAGE] = _STAGE_AXIS

    if _present(mesh, _MODEL_AXIS):
        m = mesh.shape[_MODEL_AXIS]
        if _divides(d_ff, m):
            table[MLP] = _MODEL_AXIS
        if _divides(vocab, m):
            table[VOCAB] = _MODEL_AXIS
        if _divides(n_heads, m):
            table[HEADS] = _MODEL_AXIS
        if _divides(n_kv_heads, m):
            table[KV_HEADS] = _MODEL_AXIS
            table[CACHE_KV] = _MODEL_AXIS
        if _divides(d_inner, m):
            table[INNER] = _MODEL_AXIS
        if _divides(n_experts, m):
            table[EXPERT] = _MODEL_AXIS
        elif _divides(d_ff, m):
            table[EXPERT_MLP] = _MODEL_AXIS
        # Sequence/context parallelism of activations over the same
        # axis; actual divisibility is shape-dependent and re-checked
        # in `constrain`.
        table[SEQ] = _MODEL_AXIS
        table[ATTN_SEQ] = _MODEL_AXIS
        table[ACT_SEQ] = _MODEL_AXIS
    return ShardingRules(mesh=mesh, table=table)


def constrain(x: jax.Array, rules: Optional[ShardingRules],
              logical_spec) -> jax.Array:
    """``with_sharding_constraint`` under a mesh; no-op off-mesh.

    Safe to call unconditionally from model code: with ``rules=None``,
    a mesh-less rules object, or a 1-device mesh it returns ``x``
    untouched, and axes that do not divide the operand shape are
    dropped rather than producing uneven shards.
    """
    if rules is None or rules.mesh is None or rules.mesh.size == 1:
        return x
    spec = rules.spec(logical_spec, dims=tuple(x.shape))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


# --------------------------- mesh slices ------------------------------------
# Disaggregated serving (repro.serve.disagg) carves ONE device fleet
# into disjoint submeshes — a prefill slice and a decode slice — and
# ships finished KV blocks between them. The paper's device-placement
# story (loop bodies partitioned across device SETS, §3) applied at the
# mesh level: each slice gets its own Mesh + ShardingRules, and model
# code stays slice-agnostic because it only ever names logical axes.


def carve_slices(n_first: int, devices=None):
    """Split a device list into two disjoint contiguous slices.

    Returns ``(first, rest)`` — the leading ``n_first`` devices and the
    remainder. Contiguity matters: on real hardware neighbouring device
    ids share ICI links, so each slice keeps its fast interconnect and
    only the block shipment crosses the slice boundary. ``devices``
    defaults to ``jax.devices()`` (locally visible + addressable-first
    order under multi-process ``jax.distributed``).
    """
    devices = list(jax.devices() if devices is None else devices)
    if not 0 < n_first < len(devices):
        raise ValueError(
            f"carve_slices(n_first={n_first}) needs 0 < n_first < "
            f"{len(devices)} devices (both slices must be non-empty)")
    return devices[:n_first], devices[n_first:]


def slice_mesh(devices, axes=("data",), shape=None) -> Mesh:
    """Build a Mesh over an EXPLICIT device subset.

    ``jax.make_mesh`` always spans the whole fleet; a slice mesh must
    not, so this goes through ``Mesh`` directly with the devices
    reshaped to ``shape`` (default: 1-D over a single axis). Axes are
    Auto, as in ``launch.mesh._mesh``, to keep GSPMD auto-propagation
    on the slice.
    """
    import numpy as _np
    devices = list(devices)
    axes = tuple(axes)
    shape = (len(devices),) if shape is None else tuple(shape)
    if len(shape) != len(axes):
        raise ValueError(f"shape {shape} / axes {axes} rank mismatch")
    n = 1
    for s in shape:
        n *= s
    if n != len(devices):
        raise ValueError(
            f"shape {shape} wants {n} devices, got {len(devices)}")
    arr = _np.array(devices, dtype=object).reshape(shape)
    return Mesh(arr, axes,
                axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def transfer_sharding(rules: ShardingRules, mesh: Mesh,
                      dims) -> NamedSharding:
    """Destination sharding for a shipped KV-block buffer.

    The wire format is ``(L, R, n_cols, KV, block, hd)`` — layers,
    shipped rows, table columns, kv heads, block, head dim
    (``PagedKVCache.export_rows``). Placement matches the DESTINATION
    pool's K/V pools on the head axes (``CACHE_KV``/``CACHE_HD`` — cut
    when the slice mesh has a model axis) and keeps the tiny row/column
    dims replicated, so ``jax.device_put`` lands each shard exactly
    where ``import_rows``'s scatter consumes it — no resharding hop on
    the decode slice. On a data-only slice mesh every axis drops
    (divisibility) and the buffer is simply replicated over the slice.
    """
    return rules.sharding((LAYERS, None, None, CACHE_KV, None, CACHE_HD),
                          mesh, dims=tuple(dims))


def logical_to_sharding(axes: Any, rules: ShardingRules,
                        mesh: Optional[Mesh] = None) -> Any:
    """Map a pytree of logical-axis tuples to ``NamedSharding``s.

    ``axes`` is the ``Builder("axes")`` output: the parameter pytree
    with each leaf replaced by its logical spec tuple. Tuples are
    treated as leaves.
    """
    return jax.tree.map(
        lambda spec: rules.sharding(spec, mesh=mesh), axes,
        is_leaf=lambda s: isinstance(s, tuple))
