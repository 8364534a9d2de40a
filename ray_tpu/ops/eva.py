"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542, as EvaByte runs it): a query attends exactly, by softmax,
to the rows of its own window up to itself, and to ONE pooled key/value row
for every chunk of the windows before, all under one softmax.

Positions ``0..n-1``, window ``w(i) = i // window``, chunk ``c(j) = j //
chunk``.  Every complete chunk has a summary row a head, pooled from its
roped keys with two learned vectors ``phi`` and ``mu`` of the head's width
(``eva_pool_chunks``):

    a_j = softmax_j(s k_j . phi),  v~_c = sum_j a_j v_j
    b_j = softmax_j(s k_j . mu),   k~_c = sum_j b_j k_j

and query i sees ``{j: w(j) = w(i), j <= i}`` and the summaries of ``{c: c <
(window / chunk) * w(i)}`` (never a chunk of its own window).  A context of
at most one window is plain causal attention.

Three things live here:

- ``eva_pool_chunks``: whole chunks → summary rows (a prefill's every chunk
  at once; a decode step's one page of a slot whose token closes a chunk).
- ``eva_prefill_attention``: a context's attention without an ``[n, n]`` or
  ``[n, n / chunk]`` score: the windows as a batch of causal attentions
  (``ops/attention.py::mha_attention_lse``: a reshape, windows do not see
  each other's rows), the summaries of earlier windows a block of query rows
  at a time, the two merged by their log-sum-exp.
- ``EvaCacheMap``: what the serve engine's cache holds for such a layer and
  where.  A slot's rows are NOT its tokens: at position n it reads
  ``(window / chunk) * (n // window)`` summary rows and ``n % window`` exact
  ones, writes its new row into a RING of ``window / page`` pages that every
  window overwrites from its first column, and every ``chunk`` steps pools
  one page into one summary row.  All of it is arithmetic on n, written once
  for the host's integers and numpy arrays and for the device's.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF, mha_attention, mha_attention_lse

# Query rows of one turn of the summaries' pass: its float32 scores are
# [heads, rows, summaries], 134 MB at 32 heads x 512 rows x 2,048 summaries.
SUMMARY_QUERY_BLOCK = 512


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def eva_pool_chunks(k: jax.Array, v: jax.Array, phi: jax.Array,
                    mu: jax.Array):
    """k, v: [..., chunk, H, D], whole chunks of roped keys and of values;
    phi, mu: [H, D] → (k~, v~) [..., H, D] in k's dtype: the values under
    ``softmax_j(s k_j . phi)`` and the keys under ``softmax_j(s k_j . mu)``,
    scores, softmax and sums in float32."""
    f32 = jnp.float32
    scale = k.shape[-1] ** -0.5
    kf = k.astype(f32)

    def weights(vec):  # [..., chunk, H], a softmax over the chunk
        return jax.nn.softmax(
            scale * jnp.einsum("...chd,hd->...ch", kf, vec.astype(f32)),
            axis=-2)

    v_sum = jnp.sum(weights(phi)[..., None] * v.astype(f32), axis=-3)
    k_sum = jnp.sum(weights(mu)[..., None] * kf, axis=-3)
    return k_sum.astype(k.dtype), v_sum.astype(k.dtype)


def gather_pages(pool: jax.Array, pages: jax.Array) -> jax.Array:
    """pool [layers, pages, page_size, width] (the serve engine's), pages
    [n] int32 → [layers, n, page_size, width]: page ``pages[i]`` of every
    layer, a block copied a grid step where it lies.  (Indexing the pool
    makes the chip's compiler lay the whole of it out anew around the
    gather: 4 GiB of scratch for a pool of 4 GiB.)  Neighbours that name the
    same page are copied once: a caller that wants the pages of a few of its
    n puts the scratch page, 0, in the others' place."""
    layers, _, page_size, width = pool.shape

    def copy(ids_ref, page_ref, out_ref):
        out_ref[...] = page_ref[...]

    block = (None, None, page_size, width)
    named = pl.BlockSpec(block, lambda layer, i, ids: (layer, ids[i], 0, 0))
    return pl.pallas_call(
        copy,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(layers, pages.shape[0]),
            in_specs=[named],
            out_specs=pl.BlockSpec(block, lambda layer, i, ids: (layer, i,
                                                                 0, 0))),
        out_shape=jax.ShapeDtypeStruct(
            (layers, pages.shape[0], page_size, width), pool.dtype),
        name="eva_page_gather",
        interpret=jax.default_backend() == "cpu",
    )(pages.astype(jnp.int32), pool)


def eva_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          k_sum: jax.Array, v_sum: jax.Array, *, window: int,
                          chunk: int) -> jax.Array:
    """q, k, v: [B, n, H, D] (roped); k_sum, v_sum: [B, n // chunk, H, D],
    the summaries of the context's whole chunks in order (those of its last
    window are not read) → [B, n, H, D] in q's dtype.  Rows past
    a context's real ones (a bucket's padding) lie after every real row and
    in chunks after every chunk a real row sees: they change no real row."""
    b, n, h, d = q.shape
    if n <= window:
        return mha_attention(q, k, v, causal=True)
    if n % window:  # whole windows: rows of zeros after the context's own
        fill = ((0, 0), (0, -n % window), (0, 0), (0, 0))
        return eva_prefill_attention(
            jnp.pad(q, fill), jnp.pad(k, fill), jnp.pad(v, fill), k_sum,
            v_sum, window=window, chunk=chunk)[:, :n]
    scale = d ** -0.5
    windows, per_window = n // window, window // chunk
    as_windows = lambda x: x.reshape(b * windows, window, h, d)  # noqa: E731
    own, lse = mha_attention_lse(as_windows(q), as_windows(k), as_windows(v),
                                 causal=True)
    own = own.reshape(b, n, h, d)
    lse = lse.reshape(b, windows, h, window).transpose(0, 2, 1, 3).reshape(
        b, h, n)

    # Windows from the second on also see the summaries of the windows
    # before; the last window's summaries no row of this context reads.  The
    # first window's rows take the same turn of the loop and see none (the
    # merge then leaves them as they are): one result, put together nowhere.
    seen = (windows - 1) * per_window
    k_sum, v_sum = k_sum[:, :seen], v_sum[:, :seen]
    rows = min(window, SUMMARY_QUERY_BLOCK)
    if window % rows:
        raise ValueError(f"a window of {window} rows is not whole blocks "
                         f"of {rows} query rows")
    blocks = n // rows

    def with_summaries(args):
        at, q_blk, own_blk, lse_blk = args  # [B, rows, H, D], lse [B, H, rows]
        shown = jnp.arange(seen) < per_window * (at // window)
        s = jnp.einsum("bqhd,bchd->bhqc", q_blk, k_sum,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(shown, s, NEG_INF)
        m = jnp.maximum(jnp.max(s, axis=-1), lse_blk)  # both parts' maximum
        p = jnp.where(shown, jnp.exp(s - m[..., None]), 0.0)
        there = jnp.einsum("bhqc,bchd->bqhd", p.astype(v_sum.dtype), v_sum,
                           preferred_element_type=jnp.float32)
        here = jnp.exp(lse_blk - m)  # the window's own denominator, rescaled
        total = (here + jnp.sum(p, axis=-1)).transpose(0, 2, 1)[..., None]
        merged = own_blk.astype(jnp.float32) * here.transpose(
            0, 2, 1)[..., None] + there
        return (merged / total).astype(q.dtype)

    def by_block(x, axis):  # a block of query rows a turn
        shape = x.shape[:axis] + (blocks, rows) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    out = jax.lax.map(with_summaries, (rows * jnp.arange(blocks),
                                       by_block(q, 1), by_block(own, 1),
                                       by_block(lse, 2)))
    return jnp.moveaxis(out, 0, 1).reshape(b, n, h, d)


@dataclasses.dataclass(frozen=True)
class EvaCacheMap:
    """What a slot of the serve engine holds for a model of EVA layers, and
    where: a row of the page table is ``[summary columns | ring columns]``.
    Every method is arithmetic on a position (or an array of them, numpy's
    or the device's): ``n`` is the position of the row a step writes, which
    is the count of rows cached before it."""
    window: int
    chunk: int
    page_size: int
    max_ctx: int

    def __post_init__(self):
        if self.page_size != self.chunk:
            raise ValueError(
                f"a page is a chunk: page_size {self.page_size} is not "
                f"chunk_size {self.chunk}")
        if self.window % (self.chunk * self.page_size):
            raise ValueError(
                f"a window's {self.window // self.chunk} summaries are not "
                f"whole pages of {self.page_size}")

    @property
    def summary_pages(self) -> int:
        return _ceil_div(_ceil_div(self.max_ctx, self.chunk), self.page_size)

    @property
    def ring_pages(self) -> int:
        return _ceil_div(min(self.window, self.max_ctx), self.page_size)

    @property
    def pages_per_slot(self) -> int:
        return self.summary_pages + self.ring_pages

    @property
    def rows_per_slot(self) -> int:
        """Rows a slot holds at ``max_ctx`` positions."""
        return self.pages_per_slot * self.page_size

    # what the step at position n reads
    def summary_rows(self, n):
        return (self.window // self.chunk) * (n // self.window)

    def window_rows(self, n):
        return n % self.window

    def rows_read(self, n):
        return self.summary_rows(n) + self.window_rows(n)

    # where it writes its row
    def ring_column(self, n):
        return self.summary_pages + (n % self.window) // self.page_size

    def offset(self, n):
        return n % self.page_size

    # what it closes, and where the closed chunk's summary row goes
    def closes_chunk(self, n):
        return (n + 1) % self.chunk == 0

    def closes_window(self, n):
        return (n + 1) % self.window == 0

    def summary_column(self, n):
        return (n // self.chunk) // self.page_size

    def summary_offset(self, n):
        return (n // self.chunk) % self.page_size

    def owned(self, last: int):
        """(summary pages, ring pages) a slot owns once position ``last``
        is written: a summary page every ``chunk * page_size`` positions,
        ring pages through the first window only."""
        return ((last // self.chunk) // self.page_size + 1,
                min(last, self.window - 1) // self.page_size + 1)

    def read_table(self, table, n):
        """(table', rows): the page-table rows ``ops/paged_attention.py``
        follows for slots at positions ``n`` [slots]: a slot's summary
        pages of the windows before its own, then its ring; and how many
        rows of that are live.  The summaries read are whole pages, so the
        ring's first row follows the last summary."""
        with jax.named_scope("eva_read_table"):
            pages = self.summary_rows(n) // self.page_size  # [slots]
            col = jnp.arange(table.shape[1])[None]
            col = jnp.where(col < pages[:, None], col,
                            self.summary_pages + col - pages[:, None])
            col = jnp.minimum(col, table.shape[1] - 1)
            return (jnp.take_along_axis(table, col, axis=1),
                    self.rows_read(n))
