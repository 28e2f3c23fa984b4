"""``while_loop`` with reverse-mode automatic differentiation (paper §5.1).

Stock JAX cannot reverse-differentiate ``lax.while_loop`` (dynamic trip
count ⇒ unbounded tape). This module supplies the paper's construction:

1. the forward loop is augmented with an **iteration counter**;
2. intermediate values needed by the gradient are **pushed onto bounded
   stacks** (one per value, capacity ``max_iters`` — see
   ``repro.core.stacks`` for the contiguous-buffer lowering the paper
   anticipates for XLA);
3. the gradient of the loop is **another loop that runs the body's VJP
   the same number of iterations in reverse**, popping the stacks;
4. gradients of **loop constants** (tensors captured by the body — the
   paper's ``Enter``-as-loop-constant) are **summed across iterations**
   ("we introduce subgraphs that sum gradients eagerly into new loop
   variables"). Captured constants are made explicit with
   ``jax.closure_convert`` so they receive cotangents.

Save policies (§5.1 "save any intermediate values that the gradient loop
needs" + §5.3 memory management):

- ``"all"``      — push the body's VJP residuals each iteration: no
                   recomputation in the gradient loop (TF's default).
- ``"offload"``  — same residuals, stacks live in host memory
                   (``pinned_host``): the paper's GPU→CPU swapping,
                   TPU-style.
- ``"carry"``    — push only the loop *carry*; the gradient loop re-runs
                   the body once per iteration to rebuild residuals
                   (recompute-instead-of-save, the trade-off the paper
                   cites to Gruslys et al. [17] / Chen et al. [11]).
- ``"carry_offload"`` — carry-only stacks, host-resident: the paper's
                   Table-1 configuration (swap + recompute), and the
                   policy that lets dbrx-scale train_4k activations fit
                   16 GB HBM (EXPERIMENTS.md §Perf).

The primal (non-differentiated) path is a plain ``lax.while_loop`` with
no stacks — ``jax.custom_vjp`` only engages the augmented forward under
differentiation, mirroring how the paper only rewrites graphs for which
gradients are requested.

``parallel_iterations`` — the paper's §4.3 knob for how many iterations
may run concurrently. XLA schedules a rolled loop strictly sequentially,
so concurrency must be expressed as instruction-level parallelism: for
counted loops (``cond_fn=None``) the value is used as the ``unroll``
factor of the underlying scan. In the distributed setting the same knob
becomes the number of microbatches in flight (``repro.dist.pipeline``):
pass ``mesh=`` and, when the mesh carries a pipeline "stage" axis, the
unroll window is widened to at least one full stage rotation
(``repro.dist.pipeline.schedule_unroll``) so stage ``k`` of iteration
``i+1`` can overlap stage ``k+1`` of iteration ``i``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import stacks as stacks_lib

__all__ = ["while_loop", "fori_loop"]


def _reduce_pred(ok):
    """Scalarize a cond result: a vector predicate (per-row halt bits,
    e.g. adaptive-depth decode) keeps the loop alive while ANY holds."""
    return jnp.any(ok) if jnp.ndim(ok) else ok


def _is_inexact_leaf(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.inexact)


def _float0_zero(x):
    aval = jax.typeof(x)
    return np.zeros(aval.shape, jax.dtypes.float0)


def _zero_ct(x):
    """Zero cotangent per custom_vjp conventions (float0 for ints/bools)."""
    if _is_inexact_leaf(x):
        aval = jax.typeof(x)
        return jnp.zeros(aval.shape, aval.dtype)
    return _float0_zero(x)


def while_loop(cond_fn: Optional[Callable], body_fn: Callable, init: Any, *,
               max_iters: Optional[int] = None,
               save_policy: str = "all",
               parallel_iterations: int = 1,
               offload_shardings: Any = None,
               mesh: Any = None,
               name: str = "while") -> Any:
    """Run ``body_fn`` while ``cond_fn`` holds; reverse-differentiable.

    Args:
      cond_fn: carry -> bool. A non-scalar result is a per-row liveness
        vector (paper §3.1 data-dependent predicates): the loop keeps
        iterating while ANY element holds (reduced in-graph with
        ``jnp.any`` — the halt decision never round-trips to the host).
        ``None`` means a counted loop of exactly ``max_iters``
        iterations (for-loop semantics).
      body_fn: carry -> carry (any pytree; TensorArrays welcome).
      init: initial carry.
      max_iters: static bound on the trip count; required for
        reverse-mode AD (sizes the save-stacks) and for counted loops.
      save_policy: "all" | "offload" | "carry" | "carry_offload".
      parallel_iterations: unroll factor for counted loops (§4.3 knob).
      offload_shardings: pytree matching `init` of NamedShardings — the
        device-side shardings of the carry leaves, required for host
        offload under SPMD (the host stack keeps the same partitioning,
        memory_kind=pinned_host). Single-device callers may omit it.
      mesh: optional device mesh the loop runs under. With
        ``parallel_iterations > 1`` on a multi-device mesh carrying a
        pipeline "stage" axis, the concurrency window is routed through
        ``repro.dist.pipeline.schedule_unroll`` so the unrolled body
        copies span a full stage rotation (§4.3 concurrent iterations).
      name: frame name, for error messages.

    Returns:
      The final carry.
    """
    if save_policy not in ("all", "offload", "carry", "carry_offload"):
        raise ValueError(f"unknown save_policy {save_policy!r}")
    if not stacks_lib.host_offload_supported():
        save_policy = {"offload": "all",
                       "carry_offload": "carry"}.get(save_policy,
                                                     save_policy)
    if (save_policy in ("offload", "carry_offload")
            and offload_shardings is None and len(jax.devices()) > 1):
        # SPMD host placement needs explicit shardings; stay on device.
        save_policy = {"offload": "all",
                       "carry_offload": "carry"}[save_policy]
    elem_shardings = (None if offload_shardings is None
                      else jax.tree.leaves(
                          offload_shardings,
                          is_leaf=lambda x: x is None or hasattr(
                              x, "memory_kind")))

    if cond_fn is None:
        if max_iters is None:
            raise ValueError("counted loop (cond_fn=None) requires max_iters")
        if save_policy == "all":
            # Fast path: XLA scan with native AD (residual saving is
            # equivalent); parallel_iterations lowers to unroll.
            window = parallel_iterations
            if mesh is not None and parallel_iterations > 1:
                from ..dist import pipeline as _pipeline
                window = _pipeline.schedule_unroll(mesh,
                                                   parallel_iterations)

            def scan_body(c, _):
                return body_fn(c), None

            out, _ = jax.lax.scan(scan_body, init, None, length=max_iters,
                                  unroll=max(1, min(window, max_iters)))
            return out

    # Hoist captured tracers out of body/cond so they can be differentiated
    # (body) or threaded as residuals (cond).
    body_conv, body_consts = jax.closure_convert(body_fn, init)
    if cond_fn is None:
        cond_conv, cond_consts = None, []
    else:
        cond_conv, cond_consts = jax.closure_convert(cond_fn, init)

    run = _build_while(cond_conv, body_conv, max_iters, save_policy, name,
                       elem_shardings)
    return run(init, tuple(body_consts), tuple(cond_consts))


def fori_loop(lower, upper: int, body_fn: Callable, init: Any, *,
              save_policy: str = "all", parallel_iterations: int = 1,
              offload_shardings: Any = None, mesh: Any = None) -> Any:
    """Counted loop ``for i in [lower, upper): carry = body_fn(i, carry)``."""
    n = int(upper) - int(lower)

    def body(carry):
        i, c = carry
        return (i + 1, body_fn(i, c))

    if offload_shardings is not None:
        offload_shardings = (None, offload_shardings)
    _, out = while_loop(None, body, (jnp.asarray(lower, jnp.int32), init),
                        max_iters=n, save_policy=save_policy,
                        parallel_iterations=parallel_iterations,
                        offload_shardings=offload_shardings, mesh=mesh)
    return out


def _build_while(cond_conv, body_conv, max_iters, save_policy, name,
                 elem_shardings=None):
    """Construct the custom_vjp'd loop runner for a fixed static program."""

    offload = save_policy in ("offload", "carry_offload")
    save_carry = save_policy in ("carry", "carry_offload")
    if not save_carry:
        elem_shardings = None  # residual structure unknown a priori
    # Residual-closure treedef, captured when `fwd` is traced and consumed
    # when `bwd` is traced (bwd always traces after fwd). Kept out of the
    # residual tuple because PyTreeDefs are not JAX types.
    res_holder = {}

    def _plain(init, body_consts, cond_consts):
        def wcond(state):
            i, c = state
            ok = jnp.asarray(True)
            if max_iters is not None:
                ok = jnp.logical_and(ok, i < max_iters)
            if cond_conv is not None:
                ok = jnp.logical_and(ok, _reduce_pred(
                    cond_conv(c, *cond_consts)))
            return ok

        def wbody(state):
            i, c = state
            return (i + 1, body_conv(c, *body_consts))

        _, out = jax.lax.while_loop(
            wcond, wbody, (jnp.asarray(0, jnp.int32), init))
        return out

    @jax.custom_vjp
    def run(init, body_consts, cond_consts):
        return _plain(init, body_consts, cond_consts)

    # ---------------- forward with save-stacks -----------------------------
    def fwd(init, body_consts, cond_consts):
        if max_iters is None:
            raise ValueError(
                f"while_loop({name!r}): reverse-mode AD requires max_iters "
                "to bound the save-stacks (paper §5.1)")

        if save_carry:
            saved_shapes = [
                jax.ShapeDtypeStruct(jnp.shape(l), jnp.result_type(l))
                for l in jax.tree.leaves(init)
            ]
        else:
            def _res_shapes(c):
                _, vjp_fn = jax.vjp(body_conv, c, *body_consts)
                return tuple(jax.tree.leaves(vjp_fn))

            saved_shapes = list(jax.eval_shape(_res_shapes, init))

        stk0 = stacks_lib.make_stacks(saved_shapes, max_iters,
                                      offload=offload,
                                      elem_shardings=elem_shardings)

        def wcond(state):
            i, c, _ = state
            ok = i < max_iters
            if cond_conv is not None:
                ok = jnp.logical_and(ok, _reduce_pred(
                    cond_conv(c, *cond_consts)))
            return ok

        def wbody(state):
            i, c, stk = state
            if save_carry:
                stk = stacks_lib.stacks_push(stk, i, jax.tree.leaves(c),
                                             offload=offload,
                                             elem_shardings=elem_shardings)
                c_new = body_conv(c, *body_consts)
            else:
                c_new, vjp_fn = jax.vjp(body_conv, c, *body_consts)
                leaves, tree = jax.tree.flatten(vjp_fn)
                res_holder["tree"] = tree
                stk = stacks_lib.stacks_push(stk, i, leaves, offload=offload)
            return (i + 1, c_new, stk)

        n, out, stk = jax.lax.while_loop(
            wcond, wbody, (jnp.asarray(0, jnp.int32), init, stk0))
        return out, (stk, n, init, body_consts, cond_consts)

    # ---------------- reversed gradient loop -------------------------------
    def bwd(residuals, g_out):
        stk, n, init, body_consts, cond_consts = residuals

        init_leaves = jax.tree.leaves(init)
        init_tree = jax.tree.structure(init)
        cx_idx = [i for i, l in enumerate(init_leaves) if _is_inexact_leaf(l)]
        kx_idx = [i for i, k in enumerate(body_consts) if _is_inexact_leaf(k)]

        # Float0 placeholders for non-differentiable carry leaves.
        int_placeholders = {
            i: _float0_zero(l) for i, l in enumerate(init_leaves)
            if i not in set(cx_idx)
        }

        def full_carry_ct(g_inexact):
            full = [None] * len(init_leaves)
            for slot, g in zip(cx_idx, g_inexact):
                full[slot] = g
            for slot, z in int_placeholders.items():
                full[slot] = z
            return jax.tree.unflatten(init_tree, full)

        g_out_leaves = jax.tree.leaves(g_out)
        g_carry0 = [jnp.asarray(g_out_leaves[i]) for i in cx_idx]
        g_consts0 = [jnp.zeros(jnp.shape(body_consts[i]),
                               jnp.result_type(body_consts[i]))
                     for i in kx_idx]

        def gbody(state):
            j2, g_cx, g_kx = state
            j = n - 1 - j2  # reversed traversal (paper §5.1)
            saved = stacks_lib.stacks_read(stk, j, offload=offload,
                                           elem_shardings=elem_shardings)
            if save_carry:
                c_j = jax.tree.unflatten(init_tree, saved)
                _, vjp_fn = jax.vjp(body_conv, c_j, *body_consts)
            else:
                vjp_fn = jax.tree.unflatten(res_holder["tree"], saved)
            cts = vjp_fn(full_carry_ct(g_cx))
            d_c, d_ks = cts[0], cts[1:]
            d_c_leaves = jax.tree.leaves(
                d_c, is_leaf=lambda x: x is None)
            g_cx_new = [jnp.asarray(d_c_leaves[i]) for i in cx_idx]
            g_kx_new = [g + jnp.asarray(d_ks[slot])
                        for g, slot in zip(g_kx, kx_idx)]
            return (j2 + 1, g_cx_new, g_kx_new)

        # count UP. For counted loops (cond_conv None) the trip count is
        # exactly max_iters — a static bound, which also makes the trip
        # count visible to the HLO analyzer (analysis/hlo.py). Dynamic
        # loops bound on the actual n (XLA deletes a redundant static
        # clamp, so there is no constant to annotate in that case).
        if cond_conv is None:
            gcond = lambda s: s[0] < max_iters
        else:
            gcond = lambda s: s[0] < n
        _, g_init_x, g_consts_x = jax.lax.while_loop(
            gcond, gbody,
            (jnp.asarray(0, jnp.int32), g_carry0, g_consts0))

        # Reassemble full-structure cotangents.
        g_init_full = [_zero_ct(l) for l in init_leaves]
        for slot, g in zip(cx_idx, g_init_x):
            g_init_full[slot] = g
        g_init = jax.tree.unflatten(init_tree, g_init_full)

        g_bk = [_zero_ct(k) for k in body_consts]
        for g, slot in zip(g_consts_x, kx_idx):
            g_bk[slot] = g
        g_ck = tuple(_zero_ct(k) for k in cond_consts)
        return g_init, tuple(g_bk), g_ck

    run.defvjp(fwd, bwd)
    return run
