"""The paged-attention kernel (interpreted, on the CPU) against
``cached_attention`` on a gathered dense view, and the decode step's
structure: no dense view of the cache may come back unnoticed."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import cached_attention
from ray_tpu.ops.paged_attention import paged_attention, pool_width

PS, PP = 16, 8  # page size, pages per slot: max_ctx 128
MAX_CTX = PS * PP
# Largest |paged - dense| allowed.  float32: two orders of summation of
# the same products.  bfloat16: one unit in the last place of an output
# of size ~2 (2**-6), since either side rounds its probabilities to
# bfloat16 before the second matmul, at different scales.
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2 ** -6}


def _pool(rng, layers, slots, hkv, d, dtype, layout="shuffled"):
    """A pool with one spare page, and a table that hands every slot
    ``PP`` distinct pages: ``shuffled`` over the pool, or ``contiguous``.
    ``padded``: shuffled, and rows as wide as the engine makes them
    (whole lane registers), the columns past ``hkv * d`` holding
    anything."""
    pages = slots * PP + 1
    shape = (layers, pages, PS,
             pool_width(hkv, d) if layout == "padded" else hkv * d)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    ids = np.arange(1, pages)
    if layout != "contiguous":
        ids = rng.permutation(ids)
    return k, v, jnp.asarray(ids.reshape(slots, PP), jnp.int32)


def _dense_reference(q, k_new, v_new, k_pool, v_pool, layer, table, lengths,
                     first_page):
    """cached_attention over each slot's gathered rows, from its first
    page on: the windowed reference."""
    slots, hkv, d = q.shape[0], k_new.shape[2], q.shape[3]
    k_rows = k_pool[layer][table][..., :hkv * d].reshape(
        slots, MAX_CTX, hkv, d)
    v_rows = v_pool[layer][table][..., :hkv * d].reshape(
        slots, MAX_CTX, hkv, d)
    out = []
    for s in range(slots):
        skip = int(first_page[s]) * PS
        out.append(cached_attention(
            q[s:s + 1], k_new[s:s + 1], v_new[s:s + 1],
            k_rows[s:s + 1, skip:], v_rows[s:s + 1, skip:],
            lengths[s:s + 1] - skip))
    return jnp.concatenate(out)


MIXED = "shuffled"
CASES = {
    # name: (lengths, first_page, T, H, Hkv, dtype, the pool's layout)
    "len0": ([0, 0], None, 1, 4, 4, jnp.float32, MIXED),
    "len1": ([1, 1], None, 1, 4, 4, jnp.float32, MIXED),
    "len_ps_minus_1": ([PS - 1, PS - 1], None, 1, 4, 4, jnp.float32, MIXED),
    "len_ps": ([PS, PS], None, 1, 4, 4, jnp.float32, MIXED),
    "len_ps_plus_1": ([PS + 1, PS + 1], None, 1, 4, 4, jnp.float32, MIXED),
    "len_max_ctx_minus_1": ([MAX_CTX - 1] * 2, None, 1, 4, 4, jnp.float32,
                            MIXED),
    "mixed_with_inactive": ([0, 37, 0, MAX_CTX - 1, 5, PS], None, 1, 4, 4,
                            jnp.float32, MIXED),
    "contiguous_table": ([3, 70, 0, 127], None, 1, 4, 4, jnp.float32,
                         "contiguous"),
    "t4": ([0, 37, 124, PS], None, 4, 4, 4, jnp.float32, MIXED),
    "gqa": ([9, 0, 100, PS + 1], None, 1, 4, 2, jnp.float32, MIXED),
    "gqa_t4": ([9, 0, 100, PS + 1], None, 4, 8, 2, jnp.float32, MIXED),
    "window": ([0, 40, 100, MAX_CTX - 1], [0, 1, 3, 2], 1, 4, 4, jnp.float32,
               MIXED),
    "window_t4_gqa": ([5, 40, 100, 120], [0, 2, 5, 4], 4, 4, 2, jnp.float32,
                      MIXED),
    "bf16": ([0, 37, MAX_CTX - 1, PS], None, 1, 4, 4, jnp.bfloat16, MIXED),
    "bf16_t4_gqa_window": ([5, 40, 100, 120], [0, 2, 5, 4], 4, 4, 2,
                           jnp.bfloat16, MIXED),
    "padded_rows": ([0, 37, MAX_CTX - 1, PS], None, 1, 4, 2, jnp.float32,
                    "padded"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_paged_matches_dense(case):
    lengths, first_page, t, h, hkv, dtype, layout = CASES[case]
    rng = np.random.default_rng(len(case))
    slots, d, layers, layer = len(lengths), 16, 3, 1
    k_pool, v_pool, table = _pool(rng, layers, slots, hkv, d, dtype, layout)
    q = jnp.asarray(rng.standard_normal((slots, t, h, d)), dtype)
    k_new = jnp.asarray(rng.standard_normal((slots, t, hkv, d)), dtype)
    v_new = jnp.asarray(rng.standard_normal((slots, t, hkv, d)), dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    first = None if first_page is None else jnp.asarray(first_page, jnp.int32)
    got = jax.jit(functools.partial(paged_attention, layer=layer))(
        q, k_new, v_new, k_pool, v_pool, table=table, lengths=lengths,
        first_page=first)
    want = _dense_reference(q, k_new, v_new, k_pool, v_pool, layer, table,
                            lengths, first_page or [0] * slots)
    assert got.shape == want.shape and got.dtype == dtype
    err = jnp.max(jnp.abs(got.astype(jnp.float32)
                          - want.astype(jnp.float32)))
    assert float(err) <= TOL[dtype], (case, float(err))


def test_pages_past_the_length_are_not_read():
    """Every page a slot's length does not reach, the other layers and
    the pages before a window's first, poisoned with NaN: the result is
    finite and the same bits as with a clean pool."""
    rng = np.random.default_rng(7)
    lengths = np.array([0, 1, PS, PS + 1, 77, MAX_CTX - 1])
    first_page = np.array([0, 0, 0, 1, 2, 0])
    slots, t, h, hkv, d, layer = len(lengths), 1, 4, 2, 16, 1
    k_pool, v_pool, table = _pool(rng, 3, slots, hkv, d, jnp.float32)
    q = jnp.asarray(rng.standard_normal((slots, t, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((slots, t, hkv, d)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((slots, t, hkv, d)), jnp.float32)
    live = np.zeros(k_pool.shape[1], bool)
    for s in range(slots):
        n_pages = -(-int(lengths[s]) // PS)
        live[np.asarray(table)[s, first_page[s]:n_pages]] = True
    poison = np.full(k_pool.shape, np.nan, np.float32)
    poison[layer, live] = 0.0
    run = jax.jit(functools.partial(paged_attention, layer=layer))
    args = dict(table=table, lengths=jnp.asarray(lengths, jnp.int32),
                first_page=jnp.asarray(first_page, jnp.int32))
    clean = run(q, k_new, v_new, k_pool, v_pool, **args)
    dirty = run(q, k_new, v_new, k_pool + poison, v_pool + poison, **args)
    assert bool(jnp.all(jnp.isfinite(dirty)))
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


def test_rows_past_the_length_in_the_last_page_are_masked():
    """The last live page is read whole; what lies in it past the length
    (an earlier tenant's rows, here NaN) must not reach the result."""
    rng = np.random.default_rng(8)
    lengths = np.array([1, PS - 1, PS + 3, 0])
    slots, h, d = len(lengths), 4, 16
    k_pool, v_pool, table = _pool(rng, 1, slots, h, d, jnp.float32)
    k_np, v_np = np.array(k_pool), np.array(v_pool)
    for s, n in enumerate(lengths):
        page = np.asarray(table)[s, n // PS]
        k_np[0, page, n % PS:] = np.nan
        v_np[0, page, n % PS:] = np.nan
    q, k_new, v_new = (jnp.asarray(rng.standard_normal((slots, 1, h, d)),
                                   jnp.float32) for _ in range(3))
    run = jax.jit(functools.partial(paged_attention, layer=0))
    args = dict(table=table, lengths=jnp.asarray(lengths, jnp.int32))
    clean = run(q, k_new, v_new, k_pool, v_pool, **args)
    dirty = run(q, k_new, v_new, jnp.asarray(k_np), jnp.asarray(v_np),
                **args)
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


def _largest_intermediate(jaxpr):
    """Most elements in any value a jaxpr computes, its sub-jaxprs'
    (pjit, loops, the kernel's body) included."""
    biggest = 0
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            biggest = max(biggest, int(np.prod(var.aval.shape,
                                               dtype=np.int64)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            biggest = max(biggest, _largest_intermediate(sub))
    return biggest


@pytest.mark.parametrize("draft_window", [None, 32])
def test_decode_step_builds_no_dense_view(draft_window):
    """Structure, not speed: nothing the decode step (target, windowed
    draft, verify) computes has the ``slots * max_ctx * Hkv * D`` elements
    of one layer's dense attention view, except the updated pool it
    returns.  The pool here is smaller than that view, as in a replica
    that shares pages between slots, so the one cannot hide the other."""
    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = GPT2Config.tiny(dtype=jnp.float32, vocab_size=64)
    draft_cfg = GPT2Config.draft_of(cfg)
    model, draft = GPT2(cfg), GPT2(draft_cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    draft_params = draft.init(jax.random.PRNGKey(1), ids)["params"]
    eng = LLMEngine(model, params, max_slots=8, page_size=16, max_ctx=128,
                    num_pages=12, start=False, draft_model=draft,
                    draft_params=draft_params, spec_tokens=3,
                    draft_window=draft_window)
    slot_args = (eng._table, eng._lengths, eng._last_tok, eng._active,
                 eng._temps, eng._top_ps, eng._seeds)
    window = np.zeros((eng.max_slots, eng.spec_tokens), np.int32)
    programs = {
        "decode": (eng._decode, cfg,
                   (params, eng._k_pages, eng._v_pages) + slot_args),
        "draft": (eng._draft_decode, draft_cfg,
                  (draft_params, eng._dk_pages, eng._dv_pages) + slot_args),
        "verify": (eng._verify, cfg,
                   (params, eng._k_pages, eng._v_pages, eng._table,
                    eng._lengths, window) + slot_args[3:]),
    }
    for name, (fn, c, args) in programs.items():
        dense_view = eng.max_slots * eng.max_ctx * c.num_heads * c.head_dim
        pool = int(np.prod(args[1].shape))
        assert pool < dense_view
        biggest = _largest_intermediate(jax.make_jaxpr(fn)(*args).jaxpr)
        assert biggest < dense_view, (name, biggest, dense_view)
