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
way, a batch-minor array into that buffer's form.

``fold_tiles`` is where anakin PPO's frames are packed on a TPU (PERF.md,
PR 60): ``tile_columns`` with ``models/nature_cnn.py::pack_frames`` taken
in.  The environment's raw frames ``u8[N, H, W, 4]`` lie on the chip
channel-major with the batch in the lanes and four consecutive COLUMNS of
one channel a word; the kernel takes them as they lie (no relayout in front
of it), makes a pixel's four channels a word (a 4x4 transposition of bytes
across words, by shifts and masks), folds four by four pixels into cells
with strided stores (the padding is where it does not write), and writes a
rollout step's frames twice: the trajectory's word tiles, in place, and the
batch-minor bytes the trunk reads.  One pass over the raw bytes where the
compiler's ``pack_frames`` took four and ``tile_columns`` a fifth.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

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


_CELL = 16            # words a folded cell: 4 x 4 pixels, 4 channels a word
# Frames a grid step of ``fold_tiles``: a lane tile, which is all the strided
# store that folds takes (Mosaic: "the last dim size is not 128" at 256).
_FOLD_BLOCK = LANES
# 84 x 84 takes 24 MB; 210 x 160 compiles for a described v5e (128 MB a core)
# at 105, which no chip has run
_FOLD_VMEM = 112 << 20


class _Fold(NamedTuple):
    """Raw frames ``[h, w, 4]`` of uint8 folded four by four pixels into
    cells of 16 words (a pixel's four channels a word, word ``dy * 4 + dx``
    of its cell) behind ``top`` rows and ``left`` columns of zeros, ``ph`` x
    ``pw`` cells: ``models/nature_cnn.py::pack_frames``, as words."""
    h: int
    w: int
    top: int
    left: int
    ph: int
    pw: int

    @classmethod
    def of(cls, frame, pads) -> "_Fold":
        (h, w, _c), ((top, bottom), (left, right)) = frame, pads
        return cls(h, w, top, left, (h + top + bottom) // 4,
                   (w + left + right) // 4)

    @property
    def words(self) -> int:
        return self.ph * self.pw * _CELL

    @property
    def group(self) -> int:
        """Rows of cells that are whole chunks of 128 words."""
        return _SUBLANES // math.gcd(self.pw, _SUBLANES)

    @property
    def vmem(self) -> int:
        """Two buffers each of a block's raw frames (a row padded to whole
        32-byte sublane tiles), of its tiles and of its batch-minor form,
        and a group's cells."""
        raw = 4 * self.h * -(-self.w // 32) * 32
        tiles = _tile_rows(self.words) * LANES * 4
        cells = self.group * self.pw * _CELL * 4
        return (2 * (raw + tiles + self.words * 4) + cells) * _FOLD_BLOCK


def folds_frames(x, pads) -> bool:
    """Whether ``fold_tiles`` takes frames ``x`` ``[n, h, w, c]`` (an array
    or its shape and dtype) under ``pads`` ``((top, bottom), (left,
    right))``: uint8 with four channels, rows of whole words, padded to
    whole cells of 4 x 4 pixels, folded an item ``row_tiles`` takes, a block
    of them within the kernel's VMEM."""
    if len(x.shape) != 4 or jnp.dtype(x.dtype) != jnp.uint8:
        return False
    _n, h, w, c = x.shape
    (top, bottom), (left, right) = pads
    if c != 4 or w % 4 or (h + top + bottom) % 4 or (w + left + right) % 4:
        return False
    fold = _Fold.of(x.shape[1:], pads)
    # ... and what row_tiles takes: whole 128-byte rows once folded
    return fold.words * 4 % LANES == 0 and fold.vmem <= _FOLD_VMEM


def _bytes_by_pixel(planes):
    """Four words that hold four consecutive pixels of ONE channel each
    (byte ``i`` of ``planes[c]``: channel ``c`` of pixel ``i``) -> four
    words that hold the four channels of ONE pixel each (byte ``c`` of
    result ``i``): a 4x4 transposition of bytes across words, by masks and
    shifts, byte pairs and then halves."""
    a, b, c, d = planes
    even, low = jnp.uint32(0x00FF00FF), jnp.uint32(0x0000FFFF)
    t0 = (a & even) | ((b & even) << 8)     # a0 b0 a2 b2
    t1 = ((a >> 8) & even) | (b & ~even)    # a1 b1 a3 b3
    u0 = (c & even) | ((d & even) << 8)
    u1 = ((c >> 8) & even) | (d & ~even)
    return ((t0 & low) | (u0 << 16), (t1 & low) | (u1 << 16),
            (t0 >> 16) | (u0 & ~low), (t1 >> 16) | (u1 & ~low))


def _fold_kernel(x_ref, tiles_ref, cols_ref, cells, *, fold: _Fold):
    """One block of the batch.  x_ref [4, h, w, block] uint8, raw frames as
    the chip holds them: channel, row, column, the batch in the lanes, four
    consecutive columns a word; tiles_ref [block * k, 128] words, a frame's
    ``k`` rows one under the other; cols_ref [words * 4, block] uint8, the
    folded frames batch-minor; cells [group * pw * 16, block] words: a group
    of rows of cells, a word a row, before its transposition.  Rolled loops
    throughout: the step that calls this is traced and lowered twice at
    every start, and unrolled this body cost the PPO cell 12% of its set-up
    for 5% of the kernel's time (0.291 ms a step for 0.307; unrolling either
    loop alone gives 0.01 back: PERF.md, PR 60)."""
    block = cols_ref.shape[1]
    k = tiles_ref.shape[0] // block
    per_row = fold.w // 4                  # words a raw row
    rows = fold.group * 4                  # padded raw rows a group
    chunks = fold.group * fold.pw * _CELL // LANES
    groups = -(-fold.ph // fold.group)
    full, rest = divmod(fold.words, LANES)

    # The words no raw pixel reaches (the columns' padding) are written
    # once: the buffer is kept from step to step.
    @pl.when(pl.program_id(0) == 0)
    def _():
        cells[...] = jnp.zeros(cells.shape, cells.dtype)

    def fill(j, r):
        """Padded raw row ``r`` of group ``j`` into the words of its cells:
        word ``g`` of a raw row holds columns ``4 g .. 4 g + 3``, a stride
        of a cell's words apart once folded.  A row of the padding (above
        the frame, below it, or past the last row of cells) is zeros."""
        y = j * rows + r - fold.top
        keep = jnp.where((y >= 0) & (y < fold.h), jnp.uint32(0xFFFFFFFF),
                         jnp.uint32(0))
        y = jnp.clip(y, 0, fold.h - 1)
        pixels = _bytes_by_pixel([
            pltpu.bitcast(x_ref[c, y], jnp.uint32) & keep for c in range(4)])
        at = (r // 4 * fold.pw) * _CELL + r % 4 * 4
        for i, words in enumerate(pixels):
            to, dx = divmod(i + fold.left, 4)
            cells[pl.ds(at + to * _CELL + dx, per_row, stride=_CELL), :] = \
                words

    def write(m, i, size=LANES):
        """Chunk ``i`` of the group's words, chunk ``m`` of the frame's."""
        at = pl.multiple_of(m * (LANES * 4), LANES * 4)
        cols_ref[pl.ds(at, size * 4), :] = pltpu.bitcast(
            cells[pl.ds(pl.multiple_of(i * LANES, LANES), size), :],
            jnp.uint8)
        # (past the frame's last word the cells hold zeros: whole chunks)
        tiles_ref[pl.ds(m, block, stride=k), :] = cells[
            pl.ds(pl.multiple_of(i * LANES, LANES), LANES), :].T

    def group(j, _):
        lax.fori_loop(0, rows, lambda r, _: fill(j, r), None)

        def chunk(i, _):
            @pl.when(j * chunks + i < full)
            def _():
                write(j * chunks + i, i)

        lax.fori_loop(0, chunks, chunk, None)

    lax.fori_loop(0, groups, group, None)
    if rest:  # the last group's cells are still there
        write(full, full - (groups - 1) * chunks, rest)
    for m in range(-(-fold.words // LANES), k):  # the padding of the last tile
        tiles_ref[pl.ds(m, block, stride=k), :] = jnp.zeros(
            (block, LANES), tiles_ref.dtype)


def fold_tiles(frames: jax.Array, pads, into: jax.Array | None = None,
               at=0):
    """Raw frames ``[n, h, w, 4]`` of uint8 -> ``(tiles, cols)``, both of
    them the frames padded with zeros by ``pads`` ``((top, bottom), (left,
    right))`` and folded four by four pixels into channels, channel ``(dy *
    4 + dx) * 4 + c`` (``models/nature_cnn.py::pack_frames``, which is the
    plain definition): ``cols`` ``[h' * w' * 64, n]`` with the frames as
    columns, the form a convolution reads, and ``tiles`` ``row_tiles`` of
    the folded frames, ``[n, k, 128]``, the form ``gather_rows`` reads.  One
    kernel and one pass over the raw bytes: ``tile_columns(cols)`` with the
    fold in front of it taken in.  ``into`` and ``at`` as ``tile_columns``
    has them: the buffer written in place, handed back whole.  Interpreted
    on a CPU backend."""
    if not folds_frames(frames, pads):
        raise ValueError(f"frames {frames.shape} {frames.dtype} padded by "
                         f"{pads}: not folds_frames'")
    return _fold_tiles(frames, into, jnp.asarray(at, jnp.int32),
                       pads=tuple(map(tuple, pads)),
                       interpret=backend() == "cpu")


@functools.partial(jax.jit, static_argnames=("pads", "interpret"))
def _fold_tiles(frames, into, at, *, pads, interpret):
    n = frames.shape[0]
    fold = _Fold.of(frames.shape[1:], pads)
    k, block = _tile_rows(fold.words), _FOLD_BLOCK
    blocks = -(-n // block)
    if into is not None and (into.shape[1:] != (k, LANES)
                             or into.dtype != jnp.uint32):
        raise ValueError(f"a buffer {into.shape} {into.dtype} for frames "
                         f"folded to {fold.words} words: not row_tiles' form")
    in_place = into is not None and not (n % block or into.shape[0] % block)
    # The chip holds the frames so (the batch in the lanes, a channel's
    # columns in the sublanes): a transposition in name only.
    x = jnp.pad(frames.transpose(3, 1, 2, 0),
                ((0, 0), (0, 0), (0, 0), (0, blocks * block - n)))
    kernel = functools.partial(_fold_kernel, fold=fold)
    x_spec = pl.BlockSpec((4, fold.h, fold.w, block),
                          lambda i, *_: (0, 0, 0, i))
    cols_spec = pl.BlockSpec((fold.words * 4, block), lambda i, *_: (0, i))
    cols = jax.ShapeDtypeStruct((fold.words * 4, blocks * block), jnp.uint8)
    common = dict(
        compiler_params=pltpu.CompilerParams(
            # the cells' padding is written by the first step alone
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=fold.vmem + (8 << 20)),
        name="fold_tiles", interpret=interpret)
    scratch = [pltpu.VMEM((fold.group * fold.pw * _CELL, block), jnp.uint32)]
    if in_place:
        s = into.shape[0]
        tiles, cols = pl.pallas_call(
            # as tile_columns: the place through the index map, the buffer
            # left in HBM and the result's own
            lambda at_ref, x_ref, into_ref, *refs: kernel(x_ref, *refs),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(blocks,),
                in_specs=[x_spec, pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[pl.BlockSpec(
                    (block * k, LANES),
                    lambda i, at: (at[0] // block + i, 0)), cols_spec],
                scratch_shapes=scratch),
            out_shape=[jax.ShapeDtypeStruct((s * k, LANES), into.dtype),
                       cols],
            input_output_aliases={2: 0},
            **common)(at.reshape(1), x, into.reshape(s * k, LANES))
        return tiles.reshape(into.shape), cols
    tiles, cols = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(blocks,),
            in_specs=[x_spec],
            out_specs=[pl.BlockSpec((block * k, LANES), lambda i: (i, 0)),
                       cols_spec],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((blocks * block * k, LANES),
                                        jnp.uint32), cols],
        **common)(x)
    tiles, cols = tiles.reshape(-1, k, LANES)[:n], cols[:, :n]
    if into is not None:
        # no whole blocks of the buffer: the tiles, then a copy into it
        tiles = lax.dynamic_update_slice(into, tiles, (at, 0, 0))
    return tiles, cols


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
