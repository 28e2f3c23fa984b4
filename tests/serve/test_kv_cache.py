"""KVCache protocol unit tests: block lifecycle, layout reconstruction,
and dense/paged write-read agreement (``repro.serve.kv_cache``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import kv_cache as kvc

KEY = jax.random.PRNGKey(0)


def _mk(impl, n_rows=4, max_len=12, block=4, n_blocks=None, L=2, KV=2,
        hd=8):
    if impl == "dense":
        return kvc.DenseKVCache.create(L, n_rows, max_len, KV, hd,
                                       jnp.float32)
    return kvc.PagedKVCache.create(L, n_rows, max_len, KV, hd, jnp.float32,
                                   block=block, n_blocks=n_blocks)


# ------------------- lifecycle (paged) --------------------------------------

def test_alloc_assigns_distinct_blocks_and_owners():
    c = _mk("paged")
    rows = jnp.arange(4, dtype=jnp.int32)
    budget = jnp.asarray([5, 9, 1, 12], jnp.int32)   # -> 2, 3, 1, 3 blocks
    mask = jnp.asarray([True, True, False, True])
    c2 = c.alloc(rows, budget, mask=mask)
    table = np.asarray(c2.table)
    owner = np.asarray(c2.owner)
    # masked rows hold exactly ceil(budget/block) blocks, unmasked none
    held = [sorted(b for b in table[r] if b >= 0) for r in range(4)]
    assert [len(h) for h in held] == [2, 3, 0, 3]
    # all assigned blocks distinct, each owned by its row
    flat = [b for h in held for b in h]
    assert len(set(flat)) == len(flat)
    for r, h in enumerate(held):
        for b in h:
            assert owner[b] == r
    assert int(c2.free_count) == c.n_blocks - len(flat)


def test_free_recycles_blocks_for_next_alloc():
    c = _mk("paged", n_rows=2, max_len=8, block=4, n_blocks=4)
    rows = jnp.arange(2, dtype=jnp.int32)
    c = c.alloc(rows, jnp.asarray([8, 8], jnp.int32))     # 2 + 2 = all 4
    assert int(c.free_count) == 0
    first = sorted(np.asarray(c.table)[0].tolist())
    c = c.free(mask=jnp.asarray([True, False]))
    assert int(c.free_count) == 2
    assert (np.asarray(c.table)[0] == -1).all()
    # row 0's blocks are reusable immediately (recycled to row 1... via
    # a fresh alloc on row 0 again)
    c = c.alloc(rows, jnp.asarray([8, 0], jnp.int32),
                mask=jnp.asarray([True, False]))
    assert sorted(np.asarray(c.table)[0].tolist()) == first
    assert int(c.free_count) == 0


def test_append_to_freed_row_is_dropped():
    """A retired row whose table was freed must not corrupt recycled
    blocks (writes route to the drop index)."""
    c = _mk("paged", n_rows=2, max_len=8, block=4, n_blocks=2)
    rows = jnp.arange(2, dtype=jnp.int32)
    c = c.alloc(rows, jnp.asarray([4, 4], jnp.int32))
    k1 = jnp.ones((2, 1, 2, 8))
    c = c.append(0, None, jnp.asarray([1, 1]), k1, k1)
    pool_before = np.asarray(c.k_pool).copy()
    c = c.free(mask=jnp.asarray([True, False]))
    # both rows append; row 0 has no table -> dropped
    c2 = c.append(0, None, jnp.asarray([2, 2]), 7 * k1, 7 * k1)
    pool_after = np.asarray(c2.k_pool)
    row1_block = int(np.asarray(c.table)[1, 0])
    row0_block = 1 - row1_block
    # row 1's write landed; row 0's old block untouched
    assert (pool_after[0, row1_block, :, 1] == 7).all()
    np.testing.assert_array_equal(pool_after[0, row0_block],
                                  pool_before[0, row0_block])


# ------------------- layout agreement ---------------------------------------

@pytest.mark.parametrize("block,max_len", [(4, 12), (4, 10), (16, 10)])
def test_paged_gather_matches_dense_layout(block, max_len):
    """write_prompt + append through both impls, then gather: the paged
    reconstruction must equal the dense layout bitwise on every valid
    lane."""
    n, L, KV, hd = 3, 2, 2, 8
    dense = _mk("dense", n_rows=n, max_len=max_len)
    paged = _mk("paged", n_rows=n, max_len=max_len, block=block)
    rows = jnp.arange(n, dtype=jnp.int32)
    paged = paged.alloc(rows, jnp.full((n,), max_len, jnp.int32))

    S = 6
    k = jax.random.normal(KEY, (n, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (n, S, KV, hd))
    for layer in range(L):
        dv = dense.view_at(layer).write_prompt(k + layer, v + layer)
        pv = paged.view_at(layer).write_prompt(k + layer, v + layer)
        dense = dense.set_at(layer, dv)
        paged = paged.set_at(layer, pv)
    # per-row appends at mixed depths
    cur = jnp.asarray([7, 8, 9], jnp.int32)
    k1 = jax.random.normal(jax.random.fold_in(KEY, 2), (n, 1, KV, hd))
    dense = dense.append(1, None, cur, k1, k1)
    paged = paged.append(1, None, cur, k1, k1)

    for layer in range(L):
        dk, dvv = dense.gather(layer)
        pk, pvv = paged.gather(layer)
        assert pk.shape == dk.shape
        for r in range(n):
            valid = int(cur[r])
            np.testing.assert_array_equal(np.asarray(pk)[r, :valid],
                                          np.asarray(dk)[r, :valid])
            np.testing.assert_array_equal(np.asarray(pvv)[r, :valid],
                                          np.asarray(dvv)[r, :valid])


def test_append_honors_rows_and_mask_identically():
    """The interchangeability contract: append/write_prompt with bound
    rows (a permutation) and a mask land in the SAME cache rows for
    both implementations."""
    n, max_len, KV, hd = 3, 12, 2, 8
    rows = jnp.asarray([2, 0, 1], jnp.int32)
    mask = jnp.asarray([True, False, True])
    k1 = jax.random.normal(KEY, (n, 1, KV, hd))
    cur = jnp.asarray([3, 4, 5], jnp.int32)
    gathered = {}
    for impl in ("dense", "paged"):
        c = _mk(impl, n_rows=n, max_len=max_len)
        if impl == "paged":
            c = c.alloc(jnp.arange(n, dtype=jnp.int32),
                        jnp.full((n,), max_len, jnp.int32))
        view = c.view_at(0, rows=rows, mask=mask).append(k1, k1, cur)
        c = c.set_at(0, view)
        gathered[impl] = np.asarray(c.gather(0)[0])
    for i in range(n):
        r, pos = int(rows[i]), int(cur[i]) - 1
        if bool(mask[i]):   # masked-in rows got the write, in BOTH
            np.testing.assert_array_equal(gathered["dense"][r, pos],
                                          np.asarray(k1)[i, 0])
            np.testing.assert_array_equal(gathered["paged"][r, pos],
                                          np.asarray(k1)[i, 0])
        else:               # masked-out rows untouched (zeros)
            assert (gathered["dense"][r, pos] == 0).all()
            assert (gathered["paged"][r, pos] == 0).all()


def test_cache_rides_through_jit_and_while_loop():
    """A KVCache is a pytree: jit carries + functional updates in-graph."""
    from repro import core

    c = _mk("paged", n_rows=2, max_len=8, block=4)
    c = c.alloc(jnp.arange(2, dtype=jnp.int32),
                jnp.full((2,), 8, jnp.int32))

    @jax.jit
    def run(c):
        def body(state):
            i, c = state
            k1 = jnp.full((2, 1, 2, 8), i, jnp.float32)
            c = c.append(0, None, jnp.full((2,), i + 1), k1, k1)
            return (i + 1, c)

        return core.while_loop(lambda s: s[0] < 4, body, (jnp.int32(0), c),
                               max_iters=8, name="kv")

    i, c2 = run(c)
    k, _ = c2.gather(0)
    np.testing.assert_array_equal(np.asarray(k)[0, :4, 0, 0],
                                  np.arange(4, dtype=np.float32))


def test_set_rows_matches_masked_scatter():
    """``set_rows`` (the scheduler's slot-row write) equals the masked
    scatter it replaces, for permuted rows, negative rows and rows out
    of range."""
    rng = np.random.default_rng(0)
    vec = jnp.asarray(rng.integers(0, 100, (6, 3)), jnp.int32)
    new = jnp.asarray(rng.integers(100, 200, (5, 3)), jnp.int32)
    mask = jnp.array([True, False, True, True, True])
    for rows in ([4, 0, 2, 5, 1], [3, 1, -1, 0, 9]):
        r = jnp.asarray(rows, jnp.int32)
        want = vec.at[r].set(jnp.where(mask[:, None], new, vec[r]),
                             mode="drop")
        np.testing.assert_array_equal(
            np.asarray(kvc.set_rows(vec, r, new, mask)), np.asarray(want))
