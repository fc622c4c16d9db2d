"""The KV page write (kernels/kv_write.py, Pallas interpret mode) against
the XLA scatter it replaces (kernels/ref.py) and a plain numpy oracle:
every case must leave the whole stacked pool bit-identical."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import kv_write, ops, ref

L, HKV, PS = 3, 2, 8


def _pool(lanes, n_pages=12, seed=0, ps=PS):
    return jax.random.normal(jax.random.key(seed),
                             (L, HKV, n_pages, ps, lanes), jnp.float32)


def _oracle(pool, layer, new, tables, starts, ends):
    out = np.array(pool)
    ps = pool.shape[3]
    for r in range(new.shape[0]):
        for i in range(new.shape[1]):
            p = int(starts[r]) + i
            if p < int(ends[r]):
                page = int(tables[r, min(p // ps, tables.shape[1] - 1)])
                out[layer, :, page, p % ps] = np.asarray(new[r, i])
    return out


def _write(pool, layer, k, v, tables, starts, ends, impl):
    plan = ops.kv_write_plan(jnp.asarray(tables, jnp.int32),
                             jnp.asarray(starts, jnp.int32),
                             jnp.asarray(ends, jnp.int32), k.shape[1],
                             pool.shape[3], impl=impl)
    return ops.kv_page_write(pool, layer, k, v, plan, impl=impl)


def _check(head_dim, tables, starts, ends, n_tokens, layer=1, seed=1,
           ps=PS):
    lanes = 2 * head_dim
    pool = _pool(lanes, ps=ps)
    R = tables.shape[0]
    ks = jax.random.split(jax.random.key(seed), 2)
    k = jax.random.normal(ks[0], (R, n_tokens, HKV, head_dim))
    v = jax.random.normal(ks[1], (R, n_tokens, HKV, head_dim))
    args = (layer, k, v, tables, starts, ends)
    got = _write(pool, *args, impl="pallas")
    want = _write(pool, *args, impl="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    oracle = _oracle(pool, layer, ops.pack_kv(k, v, lanes), tables,
                     starts, ends)
    np.testing.assert_array_equal(np.asarray(got), oracle)
    return got, pool


@pytest.mark.parametrize("head_dim", [16, 64, 128])
def test_decode_rows_and_dead_rows(head_dim):
    """One token per row at lens: live rows land mid-page in their own
    pages; dead rows (lens 0, zeroed table row) go to the null page 0."""
    tables = np.array([[3, 5, 0], [7, 0, 0], [0, 0, 0], [9, 2, 11],
                       [0, 0, 0]])
    lens = np.array([11, 0, 0, 23, 0])
    got, pool = _check(head_dim, tables, lens, lens + 1, 1)
    # nothing outside the written layer moves
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(got[layer]),
                                      np.asarray(pool[layer]))


@pytest.mark.parametrize("head_dim", [64, 128])
def test_decode_rows_write_one_tile_of_a_larger_page(head_dim):
    """At page size 32 a decode step moves one 8-row tile of its page:
    tokens in the first and last tile of a page, one at a tile's last
    row, and dead rows on the null page all land exactly."""
    tables = np.array([[3, 5, 0], [7, 0, 0], [0, 0, 0], [9, 2, 11],
                       [4, 6, 0]])
    lens = np.array([33, 31, 0, 71, 15])
    _check(head_dim, tables, lens, lens + 1, 1, ps=32)
    plan = kv_write.write_plan(jnp.asarray(tables, jnp.int32),
                               jnp.asarray(lens), jnp.asarray(lens + 1), 1,
                               32)
    live = np.asarray(plan.page) > 0
    # tile of each live token: 33 -> 0, 31 -> 3, 71 -> 0, 15 -> 1
    assert sorted(np.asarray(plan.blk)[live]) == [0, 0, 1, 3]
    assert (np.asarray(plan.hi) - np.asarray(plan.lo))[live].tolist() \
        == [1, 1, 1, 1]


@pytest.mark.parametrize("head_dim", [16, 64, 128])
def test_chunks_mid_page_pad_tails_and_dead_rows(head_dim):
    """Chunks that start and end mid-page, a ragged pad tail that must
    not be written, and a dead padding row (ends 0, null table)."""
    tables = np.array([[4, 6, 8, 10], [1, 2, 3, 0], [0, 0, 0, 0]])
    starts = np.array([3, 8, 0])
    ends = np.array([3 + 12, 8 + 5, 0])
    _check(head_dim, tables, starts, ends, 12)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_two_chunks_of_one_sequence_share_a_page(head_dim):
    """Two chunks of one sequence in one launch: the first ends mid-page
    5 and the second starts there, so both write page 5; a third row of
    another sequence sits between them in the batch."""
    seq = np.array([4, 5, 9, 10])
    tables = np.stack([seq, np.array([1, 2, 3, 0]), seq])
    starts = np.array([2, 0, 13])
    ends = np.array([13, 7, 23])
    _check(head_dim, tables, starts, ends, 11)


def test_short_rows_cross_a_page_boundary():
    """Rows shorter than a page (the speculative verify chains) that
    straddle a page boundary, and pad lanes past each chain."""
    tables = np.array([[2, 3, 0], [6, 7, 8]])
    starts = np.array([6, 15])
    ends = np.array([9, 17])
    _check(64, tables, starts, ends, 4)


def test_plan_sorts_pages_and_keeps_them_adjacent():
    """Every step that touches one page runs back to back, and a step
    with nothing to write targets the null page with an empty range."""
    seq = jnp.asarray([4, 5, 9, 10], jnp.int32)
    tables = jnp.stack([seq, jnp.asarray([1, 2, 3, 0], jnp.int32), seq])
    plan = kv_write.write_plan(tables, jnp.asarray([2, 0, 13]),
                              jnp.asarray([13, 7, 23]), 11, PS)
    page, blk, row, slot, lo, hi = (np.asarray(a) for a in plan[:6])
    assert list(page) == sorted(page)
    assert ((page == 0) == (hi <= lo)).all()
    assert not blk.any()                      # a chunk step moves a page
    shared = page == 5
    assert sorted(row[shared]) == [0, 2]
    assert list(np.flatnonzero(shared)) == [int(np.argmax(shared)),
                                            int(np.argmax(shared)) + 1]
    # the two rows split page 5 at position 13: rows [2, 5) and [5, 8)
    assert sorted(zip(lo[shared], hi[shared])) == [(0, 5), (5, 8)]


def test_ref_write_matches_old_split_scatter():
    """The stacked scatter stores the same values the per-pool scatter
    stored into separate K and V pools."""
    D = 16
    pool = _pool(2 * D)
    tables = jnp.asarray([[3, 5], [7, 0]], jnp.int32)
    lens = jnp.asarray([10, 2], jnp.int32)
    k = jax.random.normal(jax.random.key(3), (2, 1, HKV, D))
    v = jax.random.normal(jax.random.key(4), (2, 1, HKV, D))
    got = ref.kv_page_write(pool, 2, ops.pack_kv(k, v, 2 * D), tables,
                            lens, lens + 1)
    page = tables[jnp.arange(2), lens // PS]
    kp = pool[2, ..., :D].at[:, page, lens % PS].set(jnp.moveaxis(k[:, 0],
                                                                 1, 0))
    vp = pool[2, ..., D:].at[:, page, lens % PS].set(jnp.moveaxis(v[:, 0],
                                                                 1, 0))
    np.testing.assert_array_equal(np.asarray(got[2, ..., :D]), kp)
    np.testing.assert_array_equal(np.asarray(got[2, ..., D:]), vp)
