# Pallas TPU kernels, one package per compute hot-spot. Each package
# is the three-file pattern of DESIGN.md §11 — ref.py (pure-jnp
# oracle) + kernel.py (Pallas, `interpret` knob) + ops.py (jit'd
# dispatch: compiled on TPU, interpret elsewhere) — with a parity
# sweep in tests/kernels/test_kernels.py.
#
# Packages: flash_attention (full-sequence causal GQA forward),
# selective_scan (mamba1 scan), lstm_cell (fused gates),
# paged_attention (gather-free block-table single-token decode),
# flash_prefill (gather-free block-table causal CHUNK prefill — the
# chunked-prefill counterpart of paged_attention).

import jax as _jax
from jax.sharding import PartitionSpec as _P


def on_tpu() -> bool:
    """Shared dispatch probe: compiled Pallas on TPU, interpret-mode
    elsewhere (every ops.py wrapper, and anything reporting which
    path ran, keys off this one helper). A backend that fails to come
    up raises here: it must never read as "not a TPU" and send the
    kernels to interpret mode in silence."""
    return _jax.devices()[0].platform == "tpu"


def per_device(fn, rows):
    """``fn`` in a form the TPU compiler accepts under the context mesh.

    A Mosaic kernel cannot be partitioned automatically: in a program
    traced under a context mesh of more than one device
    (``jax.sharding.use_abstract_mesh``) it must run inside a
    ``shard_map``. There the positional operands named in ``rows`` and
    the result are split by their leading (batch-row) dim over the
    mesh's data axes, so each device runs the kernel on its own rows;
    every other operand (a KV block pool) is replicated. A row count
    the data axes do not divide runs whole on every device. With no
    such mesh ``fn`` is returned unchanged."""
    mesh = _jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn
    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in data:
        n_data *= mesh.shape[a]

    def call(*args):
        split = n_data > 1 and args[rows[0]].shape[0] % n_data == 0
        row = _P(data) if split else _P()
        in_specs = tuple(row if i in rows else _P()
                         for i in range(len(args)))
        return _jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                              out_specs=row, check_vma=False)(*args)
    return call
