"""Selected rows of a sample-major buffer, handed back with the batch as
the minor dimension: ``rows[idx].T`` in one pass over the bytes.

The TPU compiler gives a convolution's frames the batch in the lanes, and
out of a sample-major buffer it makes them in two passes: a gather of rows,
then a transposition of everything gathered (anakin PPO's minibatch of
8,192 packed frames: ``fusion u8[8192,242,128]`` + ``copy
u8[8192,242,128]``, 2.07 ms on a v5e where the kernel here takes 0.82:
PERF.md, PR 58).  It fuses a gather and it emits a copy, and does not fuse one into
the other.  The kernel here, ``gather_rows``, does: a row is copied from HBM
by one DMA, a block of rows is transposed in VMEM as 32-bit words, and the
block is written where the batch-minor array has it.

In the chip's tiled layout a uint8 array with the batch in the lanes packs
four consecutive values of the other dimension into each lane's 32-bit word,
which is what a transposed block of words already is.  So the buffer is
kept as words (``row_tiles``: four consecutive bytes of a row a word, a row
a run of whole ``(8, 128)`` tiles of its own, one contiguous stretch of HBM),
and the kernel never moves a single byte: it transposes words and writes
them out as the bytes they are.  ``tile_columns`` is the same pass the other
way, a batch-minor array into that buffer's form: what a rollout step does
with the frames the trunk has just read.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_SUBLANES = 8
TILE_BYTES = _SUBLANES * LANES * 4  # a row is padded to whole tiles of words
# Samples a grid step: their rows are in flight together, and a step writes
# ``BLOCK / 128`` KB runs of the batch-minor array (its tiles hold 8 byte
# rows of 128 samples, the batch's tiles side by side).
BLOCK = 256


def backend() -> str:
    """The backend the kernels are built for: compiled on ``"tpu"``,
    interpreted on ``"cpu"``.  A function of its own so that a compile for a
    described chip (``tools/step_fusions.py``) can name the chip's."""
    return jax.default_backend()


def _packing(dtype) -> int:
    """Values a 32-bit word holds: 4 of uint8, 1 of a 32-bit type."""
    dtype = jnp.dtype(dtype)
    if dtype != jnp.uint8 and dtype.itemsize != 4:
        raise ValueError(f"rows of {dtype}: uint8 or a 32-bit type")
    return 4 // dtype.itemsize


def _word_dtype(dtype) -> jnp.dtype:
    return jnp.dtype(jnp.uint32 if _packing(dtype) > 1 else dtype)


def _words(width: int, dtype) -> int:
    """32-bit words in a row of ``width`` values: whole lane rows of them."""
    if width * jnp.dtype(dtype).itemsize % LANES:
        raise ValueError(f"a row of {width} {dtype}: not whole "
                         f"{LANES}-byte rows")
    return width // _packing(dtype)


def _tile_rows(words: int) -> int:
    """Sublane rows of 128 words a row takes: whole ``(8, 128)`` tiles."""
    return -(-words // (_SUBLANES * LANES)) * _SUBLANES


def _block(n: int) -> int:
    return min(BLOCK, -(-n // LANES) * LANES)


def _vmem_limit(block: int, k: int, words: int) -> int:
    """Two buffers of a block's tiles and two of its batch-minor form."""
    return 2 * block * k * LANES * 4 + 2 * words * 4 * block + (8 << 20)


def _for_chunks(words: int, put) -> None:
    """``put(m, size)`` for every chunk of 128 words of a row: a rolled loop
    over the whole ones, the last one by itself where it is short."""
    full, rest = divmod(words, LANES)

    def chunk(m, _):
        put(m, LANES)
        return 0

    if full:
        lax.fori_loop(0, full, chunk, 0)
    if rest:
        put(full, rest)


def tiles_rows(x) -> bool:
    """Whether ``row_tiles`` takes ``x`` ``[n, ...]`` (an array or its shape
    and dtype): an item is a whole number of 128-byte lane rows, of uint8 or
    of a 32-bit type."""
    dtype = jnp.dtype(x.dtype)
    if dtype != jnp.uint8 and dtype.itemsize != 4:
        return False
    size = math.prod(x.shape[1:])
    return size > 0 and size * dtype.itemsize % LANES == 0


def row_tiles(x: jax.Array) -> jax.Array:
    """``[n, ...]`` -> ``[n, k, 128]`` words, the form ``gather_rows`` reads:
    an item's values in their order, four bytes a word (little-endian, as
    ``lax.bitcast_convert_type`` has them), padded with zeros to whole
    ``(8, 128)`` tiles.  The plain definition of what ``tile_columns``
    makes on the chip, where the compiler turns bytes into words by way of
    an array four times the size."""
    if not tiles_rows(x):
        raise ValueError(f"items of {x.shape[1:]} {x.dtype}: not whole "
                         f"{LANES}-byte rows")
    x = x.reshape(x.shape[0], -1)
    if _packing(x.dtype) > 1:
        x = lax.bitcast_convert_type(x.reshape(x.shape[0], -1, 4),
                                     jnp.uint32)
    x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % (_SUBLANES * LANES))))
    return x.reshape(x.shape[0], -1, LANES)


def _gather_kernel(idx_ref, tiles_hbm, out_ref, buf, sems, *, block, words,
                   packing):
    """One block of the batch.  idx_ref [blocks * block] SMEM; tiles_hbm
    [S, k, 128] words, left in HBM; out_ref [words * packing, block] the
    batch-minor block; buf [2, block * k, 128] VMEM, a row's ``k`` sublane
    rows one under the other; sems [2]."""
    step, steps = pl.program_id(0), pl.num_programs(0)
    k = tiles_hbm.shape[1]
    slot = step % 2
    per_chunk = -(-block // -(-words // LANES))  # rows asked for a chunk

    def start(at, slot, b):
        pltpu.make_async_copy(
            tiles_hbm.at[idx_ref[at * block + b]],
            buf.at[slot, pl.ds(pl.multiple_of(b * k, _SUBLANES), k)],
            sems.at[slot]).start()

    @pl.when(step == 0)
    def _():
        lax.fori_loop(0, block, lambda b, _: start(0, 0, b), None)

    # Every row of this block: the semaphore counts bytes, a slot's worth.
    pltpu.make_async_copy(buf.at[1 - slot], buf.at[slot],
                          sems.at[slot]).wait()

    def put(m, size):
        # The next block's rows are asked for a few a chunk, under this
        # block's transposition: asked for all at once before it, the
        # descriptors alone cost a quarter of the kernel's time (1.17 ms a
        # minibatch of 8,192 frames against 0.85: PERF.md, PR 58).
        @pl.when(step + 1 < steps)
        def _():
            def ask(j, _):
                b = m * per_chunk + j

                @pl.when(b < block)
                def _():
                    start(step + 1, 1 - slot, b)

            if per_chunk <= 8:  # a frame's 5: unrolled beside the vectors
                for j in range(per_chunk):
                    ask(j, None)
            else:
                lax.fori_loop(0, per_chunk, ask, None)

        # word m * 128 + lane of every sample: sublane row m of each
        cols = buf[slot, pl.ds(m, block, stride=k), :].T[:size]
        if packing > 1:
            cols = pltpu.bitcast(cols, out_ref.dtype)
        at = pl.multiple_of(m * (LANES * packing), LANES * packing)
        out_ref[pl.ds(at, size * packing), :] = cols

    _for_chunks(words, put)


def gather_rows(tiles: jax.Array, idx: jax.Array, *, width: int,
                dtype) -> jax.Array:
    """``tiles`` [S, k, 128] (``row_tiles`` of a ``[S, width]`` buffer of
    ``dtype``), ``idx`` [B] int -> ``[width, B]`` of ``dtype``: column ``b``
    is row ``idx[b]``.  An index outside ``[0, S)`` is the caller's fault
    (the DMA reads what lies there).  Interpreted on a CPU backend."""
    return _gather_rows(tiles, idx, width=width, dtype=jnp.dtype(dtype),
                        interpret=backend() == "cpu")


@functools.partial(jax.jit, static_argnames=("width", "dtype", "interpret"))
def _gather_rows(tiles, idx, *, width, dtype, interpret):
    words = _words(width, dtype)
    s, k, lanes = tiles.shape
    if lanes != LANES or k != _tile_rows(words) \
            or tiles.dtype != _word_dtype(dtype):
        raise ValueError(f"tiles {tiles.shape} {tiles.dtype} for rows of "
                         f"{width} {dtype}: not row_tiles' form")
    n = idx.shape[0]
    block = _block(n)
    blocks = -(-n // block)
    idx = jnp.pad(idx.astype(jnp.int32), (0, blocks * block - n))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block=block, words=words,
                          packing=_packing(dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((width, block), lambda i, *_: (0, i)),
            scratch_shapes=[pltpu.VMEM((2, block * k, LANES), tiles.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((width, blocks * block), dtype),
        compiler_params=pltpu.CompilerParams(
            # a block's rows arrive under the block before it
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(block, k, words)),
        name="gather_rows",
        interpret=interpret,
    )(idx, tiles)
    return out if blocks * block == n else out[:, :n]


def _tile_kernel(cols_ref, out_ref, *, block, words, packing):
    """One block of the batch, the other way: cols_ref [words * packing,
    block] batch-minor -> out_ref [block * k, 128], a row's ``k`` rows one
    under the other, words."""
    k = out_ref.shape[0] // block

    def put(m, size):
        at = pl.multiple_of(m * (LANES * packing), LANES * packing)
        cols = cols_ref[pl.ds(at, size * packing), :]
        if packing > 1:
            cols = pltpu.bitcast(cols, jnp.uint32)
        if size < LANES:
            cols = jnp.concatenate(
                [cols, jnp.zeros((LANES - size, block), cols.dtype)])
        out_ref[pl.ds(m, block, stride=k), :] = cols.T

    _for_chunks(words, put)
    for m in range(-(-words // LANES), k):  # the padding of the last tile
        out_ref[pl.ds(m, block, stride=k), :] = jnp.zeros(
            (block, LANES), out_ref.dtype)


def tile_columns(cols: jax.Array, into: jax.Array | None = None,
                 at=0) -> jax.Array:
    """``cols`` ``[width, n]``, items as columns (how the chip holds a
    batch of frames) -> ``row_tiles(cols.T)``, ``[n, k, 128]``, by one
    kernel: the transposition ``gather_rows`` undoes, a block of 32-bit
    words at a time.  With ``into``, a buffer ``[S, k, 128]`` of such tiles:
    written over its items ``at .. at + n`` in place (the kernel's result IS
    the buffer, so a loop that fills one a step at a time copies nothing;
    ``at`` a multiple of ``n``), the whole buffer handed back.  Interpreted
    on a CPU backend."""
    return _tile_columns(cols, into, jnp.asarray(at, jnp.int32),
                         interpret=backend() == "cpu")


@functools.partial(jax.jit, static_argnames=("interpret",))
def _tile_columns(cols, into, at, *, interpret):
    width, n = cols.shape
    words = _words(width, cols.dtype)
    k, block = _tile_rows(words), _block(n)
    blocks = -(-n // block)
    tiles = jax.ShapeDtypeStruct((blocks * block, k, LANES),
                                 _word_dtype(cols.dtype))
    if into is not None and (into.shape[1:] != tiles.shape[1:]
                             or into.dtype != tiles.dtype):
        raise ValueError(f"a buffer {into.shape} {into.dtype} for items of "
                         f"{width} {cols.dtype}: not row_tiles' form")
    in_place = into is not None and not (n % block or into.shape[0] % block)
    kernel = functools.partial(_tile_kernel, block=block, words=words,
                               packing=_packing(cols.dtype))
    common = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(block, k, words)),
        name="tile_columns", interpret=interpret)
    if in_place:
        s = into.shape[0]
        return pl.pallas_call(
            # the place is read by the result's index map, and the buffer,
            # left in HBM, is the result's own: the body sees neither
            lambda at_ref, cols_ref, into_ref, out_ref: kernel(cols_ref,
                                                               out_ref),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(blocks,),
                in_specs=[pl.BlockSpec((width, block), lambda i, at: (0, i)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(
                    (block * k, LANES),
                    lambda i, at: (at[0] // block + i, 0))),
            out_shape=jax.ShapeDtypeStruct((s * k, LANES), into.dtype),
            # operands count the prefetched scalar: the buffer is the third
            input_output_aliases={2: 0},
            **common)(at.reshape(1), cols, into.reshape(s * k, LANES)
                      ).reshape(into.shape)
    out = pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((width, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block * k, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks * block * k, LANES),
                                       tiles.dtype),
        **common)(jnp.pad(cols, ((0, 0), (0, blocks * block - n)))
                  ).reshape(tiles.shape)[:n]
    if into is None:
        return out
    # no whole blocks of the buffer: the tiles, then a copy into it
    return lax.dynamic_update_slice(into, out, (at, 0, 0))


def empty_tiles(n: int, width: int, dtype) -> jax.Array:
    """A buffer for ``n`` items of ``width`` values of ``dtype`` in
    ``row_tiles``' form, ``[n, k, 128]``, with nothing written: for
    ``tile_columns(into=)`` to fill (zeros would be a pass over all of it;
    on a CPU backend it is zeros)."""
    shape = (n, _tile_rows(_words(width, dtype)), LANES)
    if backend() == "cpu":
        return jnp.zeros(shape, _word_dtype(dtype))
    return pl.pallas_call(
        lambda out_ref: None,
        out_shape=jax.ShapeDtypeStruct(shape, _word_dtype(dtype)),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name="empty_tiles")()
