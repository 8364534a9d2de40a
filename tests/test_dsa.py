"""Learned sparse attention (``ops/dsa.py``) on the CPU at tiny widths: the
index scores and the selection against their plain definition, ties
included; the selection over a whole context as a mask in blocks of query
rows; one new token a slot against the serve engine's page pool (the index
kernel interpreted, ``lax.top_k``, the gather by row) against the masked
softmax; the two forms selecting the same rows; and, where ``topk`` reaches
the context, ``ops/mla.py``'s dense result through ``paged_attention``
(ISSUE 53).
"""
import numpy as np
import pytest


def _blocks(monkeypatch, block_q, block_k):
    """The context form's blocks at a test's size (module constants of
    ``ops/dsa.py``: 256 query rows, 1,024 key rows a step)."""
    from ray_tpu.ops import dsa

    monkeypatch.setattr(dsa, "BLOCK_Q", block_q)
    monkeypatch.setattr(dsa, "BLOCK_K", block_k)


def _normal(seed, *shape):
    import jax

    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _plain_scores(q_idx, w_idx, k_idx):
    """I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]), one pair at a time."""
    q, w, k = (np.asarray(a, np.float64) for a in (q_idx, w_idx, k_idx))
    out = np.zeros((q.shape[0], k.shape[0]))
    for t in range(q.shape[0]):
        for s in range(k.shape[0]):
            out[t, s] = sum(w[t, j] * max(q[t, j] @ k[s], 0.0)
                            for j in range(q.shape[1]))
    return out


def _plain_selection(scores, topk):
    """S_t: the min(topk, t + 1) rows s <= t of largest scores[t, s], ties
    to the lower s: sorted by (-score, s), a row at a time."""
    mask = np.zeros(scores.shape, bool)
    for t in range(scores.shape[0]):
        order = sorted(range(min(t + 1, scores.shape[1])),
                       key=lambda s: (-scores[t, s], s))
        mask[t, order[:topk]] = True
    return mask


def _masked_softmax(q, k, v, mask, scale):
    """q [T, H, D], k [S, H, D], v [S, H, V], mask [T, S] → [T, H, V]."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("thd,shd->hts", q, k) * scale
    s = jnp.where(mask[None], s, -jnp.inf)
    return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), v)


def test_index_scores_are_the_definition():
    from ray_tpu.ops.dsa import index_scores

    q, w, k = _normal(0, 7, 3, 16), _normal(1, 7, 3), _normal(2, 11, 16)
    np.testing.assert_allclose(index_scores(q, w, k),
                               _plain_scores(q, w, k), atol=1e-5)


@pytest.mark.parametrize("case", ["random", "all_equal", "tied_at_the_edge",
                                  "fewer_candidates_than_topk"])
def test_the_selection_mask_is_lax_top_k_s_set_ties_to_the_lower_row(case):
    """``topk_mask`` keeps what ``lax.top_k`` returns the indices of, and
    that is the plain definition: among equal scores the lower s."""
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops.dsa import topk_mask

    n, topk = 24, 6
    scores = np.asarray(_normal(3, n, n))
    if case == "all_equal":
        scores = np.zeros((n, n), np.float32)
    elif case == "tied_at_the_edge":  # five rows share the last place
        scores = np.round(scores * 2) / 2 + 0.0  # no -0.0: a sort (and
        # the mask) puts it below 0.0, the definition's comparison does not
    elif case == "fewer_candidates_than_topk":
        topk = 40
    causal = np.tril(np.ones((n, n), bool))
    masked = jnp.where(causal, scores, -jnp.inf)
    got = np.asarray(topk_mask(masked, topk))
    want = _plain_selection(scores, topk)
    np.testing.assert_array_equal(got, want)
    vals, idx = lax.top_k(masked, min(topk, n))
    by_index = np.zeros((n, n), bool)
    for t in range(n):
        by_index[t, np.asarray(idx[t])[np.asarray(vals[t]) > -np.inf]] = True
    np.testing.assert_array_equal(got, by_index)
    assert (got.sum(-1) == np.minimum(np.arange(n) + 1, topk)).all()


def test_the_kth_largest_is_found_bit_by_bit():
    """``kth_largest_key`` against a sort, over values of both signs, both
    zeros, both infinities and repeats; the keys order as the floats do."""
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import _ordered_keys, kth_largest_key

    x = np.asarray(_normal(9, 6, 40)) * 3
    x[0, :5] = [0.0, -0.0, np.inf, -np.inf, 0.0]
    x[1] = np.round(x[1])
    x[2] = -np.abs(x[2])
    keys = _ordered_keys(jnp.asarray(x))
    order = np.argsort(np.asarray(keys), axis=-1, kind="stable")
    assert (np.diff(np.take_along_axis(x, order, -1), axis=-1) >= 0).all()
    for k in (1, 7, 40):
        got = np.asarray(kth_largest_key(keys, k))[:, 0]
        want = np.sort(np.asarray(keys), axis=-1)[:, -k]
        np.testing.assert_array_equal(got, want)


def _context(length, heads=3, nope=12, rope=8, vd=16, j=2, d=16, seed=0,
             batch=2):
    ks = iter(range(seed * 10, seed * 10 + 10))
    q_nope = _normal(next(ks), batch, length, heads, nope)
    q_rope = _normal(next(ks), batch, length, heads, rope)
    kv = _normal(next(ks), batch, length, heads, nope + vd)
    k_rope = _normal(next(ks), batch, length, rope)
    q_idx = _normal(next(ks), batch, length, j, d)
    w_idx = _normal(next(ks), batch, length, j)
    k_idx = _normal(next(ks), batch, length, d)
    return q_nope, q_rope, kv, k_rope, q_idx, w_idx, k_idx


@pytest.mark.parametrize("length,topk,block_q,block_k", [
    (40, 8, 8, 16),    # five query blocks, three key blocks, the last padded
    (37, 8, 8, 16),    # a context that fills neither kind of block
    (24, 100, 8, 8),   # topk past the context: plain causal attention
    (50, 16, 16, 16),  # one key block a query block
    (5, 2, 8, 16),     # shorter than a block
])
def test_a_context_attends_over_each_row_s_own_selection(
        monkeypatch, length, topk, block_q, block_k):
    """``dsa_prefill_attention`` against the definition: every row's
    softmax over its own S_t, nothing else; the mask it keeps is S_t."""
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import dsa_prefill_attention

    _blocks(monkeypatch, block_q, block_k)
    q_nope, q_rope, kv, k_rope, q_idx, w_idx, k_idx = _context(length)
    nope = q_nope.shape[-1]
    out, chosen = dsa_prefill_attention(
        q_nope, q_rope, kv, k_rope, q_idx, w_idx, k_idx, topk, 0.2,
        keep=True)
    for b in range(q_nope.shape[0]):
        want_mask = _plain_selection(
            _plain_scores(q_idx[b], w_idx[b], k_idx[b]), topk)
        np.testing.assert_array_equal(np.asarray(chosen[b]), want_mask)
        k = jnp.concatenate([kv[b, ..., :nope], jnp.broadcast_to(
            k_rope[b, :, None], q_rope[b].shape)], -1)
        want = _masked_softmax(
            jnp.concatenate([q_nope[b], q_rope[b]], -1), k, kv[b, ..., nope:],
            jnp.asarray(want_mask), 0.2)
        np.testing.assert_allclose(out[b], want, atol=2e-5)
    alone = dsa_prefill_attention(
        q_nope, q_rope, kv, k_rope, q_idx, w_idx, k_idx, topk, 0.2)
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(out))


def test_a_stretch_of_query_rows_is_the_same_rows_of_the_whole(monkeypatch):
    """``first``: the queries of rows 16 .. 40 alone, against every key,
    give those rows of the whole context's result, traced or not."""
    import jax

    from ray_tpu.ops.dsa import dsa_prefill_attention

    _blocks(monkeypatch, 8, 16)
    args = _context(40, seed=1)
    whole = dsa_prefill_attention(*args, 8, 0.2)
    rows = slice(16, 40)
    q_nope, q_rope, kv, k_rope, q_idx, w_idx, k_idx = args

    def stretch(first):
        return dsa_prefill_attention(
            q_nope[:, rows], q_rope[:, rows], kv, k_rope, q_idx[:, rows],
            w_idx[:, rows], k_idx, 8, 0.2, first=first)

    np.testing.assert_allclose(stretch(16), whole[:, rows], atol=1e-6)
    np.testing.assert_allclose(jax.jit(stretch)(16), whole[:, rows],
                               atol=1e-6)


def test_blocks_of_nothing_but_padding_are_skipped(monkeypatch):
    """``real``: the rows from there on are a bucket's padding; a block of
    query rows that holds nothing else comes back as zeros (it is not
    computed), every row before it as without."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import dsa_prefill_attention

    _blocks(monkeypatch, 8, 16)
    args = _context(48, seed=2)
    whole = dsa_prefill_attention(*args, 8, 0.2)
    cut = jax.jit(lambda real: dsa_prefill_attention(
        *args, 8, 0.2, real=real))(jnp.asarray(19))
    np.testing.assert_allclose(cut[:, :24], whole[:, :24], atol=1e-6)
    assert not np.asarray(cut[:, 24:]).any()


# against the page pool -------------------------------------------------------
def _pool(lengths, heads=4, width=40, rank=24, d=16, ps=8, pages_a_slot=8,
          layers=2, layer=1, seed=0):
    """A pool whose slots hold ``lengths`` cached rows through a shuffled
    page table, NaN in every row no slot holds and in every other layer:
    (k_pool, v_pool, table, the rows a slot [slots][length, ...])."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import pool_width

    rng = np.random.default_rng(seed)
    slots = len(lengths)
    n_pages = slots * pages_a_slot + 1
    wide = pool_width(1, width)
    k_pool = np.full((layers, n_pages, ps, wide), np.nan, np.float32)
    v_pool = np.full((layers, n_pages, ps, wide), np.nan, np.float32)
    table = rng.permutation(np.arange(1, n_pages)).reshape(
        slots, pages_a_slot).astype(np.int32)
    held = []
    for s, n in enumerate(lengths):
        k_rows = rng.standard_normal((n, width)).astype(np.float32)
        c = k_rows[:, :rank]
        keys = rng.standard_normal((n, d)).astype(np.float32)
        for t in range(n):
            page, off = table[s, t // ps], t % ps
            k_pool[layer, page, off, :width] = k_rows[t]
            v_pool[layer, page, off, :rank] = c[t]
            v_pool[layer, page, off, rank:rank + d] = keys[t]
        held.append((k_rows, keys))
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table), held


def test_the_index_kernel_scores_the_cached_keys_where_they_lie():
    """``cached_index_scores`` (interpreted) against ``index_scores`` over
    each slot's own keys; ``-inf`` from the slot's length on; NaN in every
    page no slot holds and every row past a length is never read."""
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import cached_index_scores, index_scores

    lengths = [37, 0, 64, 9]
    _, v_pool, table, held = _pool(lengths)
    q_idx, w_idx = _normal(5, 4, 2, 16), _normal(6, 4, 2)
    got = cached_index_scores(q_idx, w_idx, v_pool, jnp.asarray(1), table,
                              jnp.asarray(lengths), first_col=24)
    assert got.shape == (4, 64)
    for s, n in enumerate(lengths):
        assert np.all(np.isneginf(np.asarray(got[s, n:])))
        if n:
            want = index_scores(q_idx[s][None], w_idx[s][None],
                                jnp.asarray(held[s][1]))[0]
            np.testing.assert_allclose(got[s, :n], want, atol=1e-5)


def _new_token(slots, heads=4, width=40, rank=24, j=2, d=16, seed=7):
    q = _normal(seed, slots, 1, heads, width)
    k_new = _normal(seed + 1, slots, 1, 1, width)
    v_new = k_new.at[..., rank:].set(0.0)  # [c | 0], as latent_rows
    index = (_normal(seed + 2, slots, 1, j, d), _normal(seed + 3, slots, 1, j),
             _normal(seed + 4, slots, 1, d))
    return q, k_new, v_new, index


@pytest.mark.parametrize("topk", [8, 16, 200])
def test_a_decode_step_attends_over_the_selected_rows_alone(topk):
    """``sparse_paged_attention`` against the definition: each slot's new
    token scores its cached index keys and its own, keeps the ``topk`` best
    and runs its softmax over those latent rows; the count is
    ``min(length + 1, topk)``; an unselected row's value never shows."""
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import sparse_paged_attention

    lengths = [37, 0, 63, 9]
    k_pool, v_pool, table, held = _pool(lengths)
    q, k_new, v_new, index = _new_token(4)
    out, rows = sparse_paged_attention(
        q, k_new, v_new, k_pool=k_pool, v_pool=v_pool, layer=1, table=table,
        lengths=jnp.asarray(lengths), index=index, topk=topk, sm_scale=0.3,
        rank=24)
    assert out.shape == (4, 1, 4, 24)
    np.testing.assert_array_equal(
        rows, [min(n + 1, topk) for n in lengths])
    for s, n in enumerate(lengths):
        keys = np.concatenate([held[s][1], np.asarray(index[2][s])])
        scores = _plain_scores(index[0][s, 0][None], index[1][s, 0][None],
                               keys)[0]
        order = sorted(range(n + 1), key=lambda i: (-scores[i], i))[:topk]
        mask = np.zeros((1, n + 1), bool)
        mask[0, order] = True
        latent = np.concatenate([held[s][0], np.asarray(k_new[s, 0])])
        k = jnp.asarray(latent)[:, None]                    # ONE KV head
        want = _masked_softmax(
            q[s], jnp.broadcast_to(k, (n + 1, 4, 40)),
            jnp.broadcast_to(k[..., :24], (n + 1, 4, 24)),
            jnp.asarray(mask), 0.3)
        np.testing.assert_allclose(out[s], want, atol=2e-5)
    # a caller that keeps the selection gets the same result and, a slot,
    # the positions in the order of their scores (the token's own: its
    # length), -1 past the rows there are
    again, rows_again, places = sparse_paged_attention(
        q, k_new, v_new, k_pool=k_pool, v_pool=v_pool, layer=1, table=table,
        lengths=jnp.asarray(lengths), index=index, topk=topk, sm_scale=0.3,
        rank=24, keep=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))
    np.testing.assert_array_equal(rows_again, rows)
    for s, n in enumerate(lengths):
        keys = np.concatenate([held[s][1], np.asarray(index[2][s])])
        scores = _plain_scores(index[0][s, 0][None], index[1][s, 0][None],
                               keys)[0]
        order = sorted(range(n + 1), key=lambda i: (-scores[i], i))[:topk]
        got = np.asarray(places[s])
        np.testing.assert_array_equal(got[:len(order)], order)
        assert (got[len(order):] == -1).all()


def test_topk_past_the_context_is_the_dense_paged_attention():
    """With every cached row selected the sparse hook gives what the paged
    kernel gives over the same pool: ``mla_absorbed``'s dense result."""
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import sparse_paged_attention
    from ray_tpu.ops.paged_attention import paged_attention

    lengths = [37, 5, 63, 9]
    k_pool, v_pool, table, _ = _pool(lengths)
    # the paged kernel reads whole pages: no NaN where it looks
    k_pool, v_pool = jnp.nan_to_num(k_pool), jnp.nan_to_num(v_pool)
    # ... and takes the V row's first columns for the values: [c | 0]
    v_dense = v_pool.at[..., 24:].set(0.0)
    q, k_new, v_new, index = _new_token(4)
    sparse, _ = sparse_paged_attention(
        q, k_new, v_new, k_pool=k_pool, v_pool=v_pool, layer=1, table=table,
        lengths=jnp.asarray(lengths), index=index, topk=64 + 1, sm_scale=0.3,
        rank=24)
    dense = paged_attention(q, k_new, v_new, k_pool, v_dense, 1, table,
                            jnp.asarray(lengths), sm_scale=0.3)[..., :24]
    np.testing.assert_allclose(sparse, dense, atol=2e-5)


def test_the_two_forms_select_the_same_rows(monkeypatch):
    """Row t of a context, selected by the prefill form's mask, and the
    same row as a new token over t cached rows, selected by the decode
    form: the same S_t, for every t, read off what each form attends to
    (values that are one-hot in the row's position)."""
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import dsa_prefill_attention, sparse_paged_attention

    _blocks(monkeypatch, 8, 16)
    length, topk, rank, d, ps = 48, 8, 48, 16, 8
    _, q_rope, _, k_rope, q_idx, w_idx, k_idx = _context(
        length, heads=1, rope=8, j=2, d=d, seed=3, batch=1)
    # keys that say nothing (a uniform softmax over S_t), values that name
    # their row: what comes back is S_t's indicator over |S_t|
    zeros = jnp.zeros((1, length, 1, 4))
    values = jnp.eye(length)[None, :, None]
    _, chosen = dsa_prefill_attention(
        zeros, 0 * q_rope, jnp.concatenate([zeros, values], -1), k_rope,
        q_idx, w_idx, k_idx, topk, 1.0, keep=True)
    chosen = np.asarray(chosen[0])
    # the decode form, a row at a time: the pool holds rows 0 .. t-1
    pages = length // ps
    k_pool = jnp.zeros((1, pages + 1, ps, 128))
    v_pool = jnp.zeros((1, pages + 1, ps, 128))
    table = jnp.arange(1, pages + 1)[None]
    k_pool = k_pool.at[0, 1:, :, :rank].set(
        jnp.eye(length).reshape(pages, ps, length))
    v_pool = v_pool.at[0, 1:, :, rank:rank + d].set(
        k_idx[0].reshape(pages, ps, d))
    for t in range(length):
        own = jnp.zeros((1, 1, 1, 128)).at[..., t].set(1.0)
        out, n, places = sparse_paged_attention(
            jnp.zeros((1, 1, 1, 128)), own, own, k_pool=k_pool, v_pool=v_pool,
            layer=0, table=table, lengths=jnp.asarray([t]),
            index=(q_idx[:, t:t + 1], w_idx[:, t:t + 1], k_idx[:, t:t + 1]),
            topk=topk, sm_scale=1.0, rank=rank, keep=True)
        picked = np.asarray(out[0, 0, 0]) > 0
        np.testing.assert_array_equal(picked, chosen[t], err_msg=f"row {t}")
        assert int(n[0]) == picked.sum() == min(t + 1, topk)
        # ... and the positions it says it selected are the rows it read
        places = np.asarray(places[0])
        np.testing.assert_array_equal(np.sort(places[places >= 0]),
                                      np.flatnonzero(picked))
        assert (places[int(n[0]):] == -1).all()


def test_a_window_of_pages_is_refused():
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import sparse_paged_attention

    k_pool, v_pool, table, _ = _pool([3])
    q, k_new, v_new, index = _new_token(1)
    with pytest.raises(ValueError, match="first_page"):
        sparse_paged_attention(
            q, k_new, v_new, k_pool=k_pool, v_pool=v_pool, layer=1,
            table=table, lengths=jnp.asarray([3]), index=index, topk=4,
            sm_scale=1.0, rank=24, first_page=jnp.zeros((1,), jnp.int32))
