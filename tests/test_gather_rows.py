"""``ops/gather_rows.py``: selected rows of a sample-major buffer handed
back batch-minor (``rows[idx].T``), the same pass the other way
(``tile_columns``), and that pass with the four-by-four fold of raw frames
taken in (``fold_tiles``), all in interpret mode against plain
``jax.numpy``; and ``NatureCNN`` on what the kernels hand over, packed
frames with the batch last, against the same frames batch-first."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.nature_cnn import (NatureCNN, _pads, folds_tiled,
                                       pack_frames, pack_frames_tiled)
from ray_tpu.ops import gather_rows as rows_op
from ray_tpu.rllib.core.rl_module import RLModuleSpec

# a row's width in bytes: one lane row, three, a packed 84x84x4 frame
WIDTHS = [128, 384, 30976]
DTYPES = [np.uint8, np.int32, np.float32]


def _rows(n, width_bytes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    width = width_bytes // np.dtype(dtype).itemsize
    if dtype == np.float32:
        return rng.normal(size=(n, width)).astype(dtype)
    return rng.integers(0, 256, (n, width)).astype(dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("width_bytes", WIDTHS)
@pytest.mark.parametrize("batch", [5, 128, 300], ids=lambda b: f"batch{b}")
def test_gather_rows_is_the_plain_gather_transposed(width_bytes, dtype, batch):
    """Repeats, the first and the last row, a batch that is a block, less
    than one, and more than one with a remainder."""
    rows = _rows(11, width_bytes, dtype)
    idx = np.random.default_rng(1).integers(0, 11, batch)
    idx[:4] = [10, 0, 0, 10]
    tiles = rows_op.row_tiles(jnp.asarray(rows))
    assert tiles.shape[1] % 8 == 0 and tiles.shape[2] == 128
    assert tiles.dtype == (np.uint32 if dtype == np.uint8 else dtype)
    got = rows_op.gather_rows(tiles, jnp.asarray(idx), width=rows.shape[1],
                              dtype=dtype)
    assert got.shape == (rows.shape[1], batch) and got.dtype == dtype
    assert np.array_equal(np.asarray(got), rows[idx].T)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("width_bytes", WIDTHS)
@pytest.mark.parametrize("n", [5, 256, 300], ids=lambda n: f"items{n}")
def test_tile_columns_is_row_tiles_of_the_transpose(width_bytes, dtype, n):
    rows = _rows(n, width_bytes, dtype, seed=2)
    got = rows_op.tile_columns(jnp.asarray(rows.T))
    want = rows_op.row_tiles(jnp.asarray(rows))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype,width_bytes,n,total,at", [
    (np.uint8, 30976, 256, 768, 256),   # whole blocks: in place
    (np.uint8, 384, 128, 384, 256),
    (np.int32, 384, 130, 390, 130),     # no whole blocks: tiles, then a copy
    (np.float32, 128, 5, 20, 7),
], ids=["frames", "a-block", "a-block-and-two", "five"])
def test_tile_columns_into_a_buffer_writes_its_items_and_no_other(
        dtype, width_bytes, n, total, at):
    rows, held = _rows(n, width_bytes, dtype, 5), _rows(total, width_bytes,
                                                        dtype, 6)
    buffer = rows_op.row_tiles(jnp.asarray(held))
    got = rows_op.tile_columns(jnp.asarray(rows.T), into=buffer, at=at)
    want = np.asarray(buffer).copy()
    want[at:at + n] = np.asarray(rows_op.row_tiles(jnp.asarray(rows)))
    assert got.shape == buffer.shape and got.dtype == buffer.dtype
    assert np.array_equal(np.asarray(got), want)
    empty = rows_op.empty_tiles(total, rows.shape[1], dtype)
    assert empty.shape == buffer.shape and empty.dtype == buffer.dtype
    with pytest.raises(ValueError, match="row_tiles"):
        rows_op.tile_columns(jnp.asarray(rows.T), into=buffer[:, :4], at=0)


def _raw(n, shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 256, (n, *shape, 4), dtype=np.uint8))


@pytest.mark.parametrize("shape,n,total,at", [
    ((84, 84), 128, 512, 0),      # whole blocks: in place, at several places
    ((84, 84), 128, 512, 128),
    ((84, 84), 128, 512, 384),
    ((84, 84), 130, 390, 130),    # a block and two: tiles, then a copy
    ((84, 84), 5, 20, 7),
    ((210, 160), 3, 9, 3),        # three rows on top, not two: pads otherwise
    ((36, 40), 2, 4, 2),          # one group of cells, first and last at once
], ids=["frames-at0", "frames-at128", "frames-at384", "a-block-and-two",
        "five", "210x160", "36x40"])
def test_fold_tiles_is_tile_columns_of_the_packed_frames(shape, n, total, at):
    """Byte for byte what ``tile_columns`` makes of ``pack_frames``' result,
    and the packed frames themselves with the batch last; every item of the
    buffer outside ``[at, at + n)`` as it was."""
    frames, pads = _raw(n, shape, seed=7), _pads(*shape)
    packed = pack_frames(frames)
    cols = packed.reshape(n, -1).T
    held = np.random.default_rng(8).integers(
        0, 256, (total, cols.shape[0])).astype(np.uint8)
    buffer = rows_op.row_tiles(jnp.asarray(held))
    assert rows_op.folds_frames(frames, pads) and folds_tiled((*shape, 4))
    tiles, seen = rows_op.fold_tiles(frames, pads, into=buffer, at=at)
    want = np.asarray(rows_op.tile_columns(cols, into=buffer, at=at))
    assert tiles.shape == buffer.shape and tiles.dtype == buffer.dtype
    assert np.array_equal(np.asarray(tiles), want)
    outside = np.r_[0:at, at + n:total]
    assert np.array_equal(np.asarray(tiles)[outside],
                          np.asarray(buffer)[outside])
    assert seen.shape == cols.shape and seen.dtype == jnp.uint8
    assert np.array_equal(np.asarray(seen), np.asarray(cols))
    # ... with no buffer: the tiles alone
    alone, seen = rows_op.fold_tiles(frames, pads)
    assert np.array_equal(np.asarray(alone), want[at:at + n])
    assert np.array_equal(np.asarray(seen), np.asarray(cols))
    # ... and as the trunk's module hands it over
    tiles, last = pack_frames_tiled(frames, into=buffer, at=at)
    assert np.array_equal(np.asarray(tiles), want)
    assert np.array_equal(np.asarray(last),
                          np.moveaxis(np.asarray(packed), 0, -1))


@pytest.mark.parametrize("shape,dtype", [
    ((2, 96, 96, 3), np.uint8),     # three channels are no word
    ((2, 84, 84, 4), np.float32),   # nor are floats bytes
    ((2, 84, 86, 4), np.uint8),     # a row of 86 columns: half a word over
    ((2, 32, 32, 4), np.uint8),     # folded, 5,184 bytes: no whole lane rows
    ((84, 84, 4), np.uint8),        # no batch
], ids=["three-channels", "float32", "86-columns", "32x32", "one-frame"])
def test_frames_that_do_not_fold_as_words_are_refused(shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype)
    pads = _pads(*shape[-3:-1])
    assert not rows_op.folds_frames(x, pads)
    with pytest.raises(ValueError, match="folds_frames"):
        rows_op.fold_tiles(jnp.zeros(shape, dtype), pads)
    if len(shape) == 4:
        assert not folds_tiled(shape[1:], dtype)


def test_fold_tiles_refuses_a_buffer_of_another_form():
    frames, pads = _raw(2, (84, 84)), _pads(84, 84)
    buffer = rows_op.row_tiles(jnp.zeros((4, 30976), jnp.uint8))
    with pytest.raises(ValueError, match="row_tiles"):
        rows_op.fold_tiles(frames, pads, into=buffer[:, :8], at=0)
    with pytest.raises(ValueError, match="row_tiles"):
        rows_op.fold_tiles(frames, pads,
                           into=buffer.astype(jnp.int32), at=0)


def test_row_tiles_keeps_a_rows_bytes_in_their_order():
    rows = _rows(3, 384, np.uint8)
    tiles = np.asarray(rows_op.row_tiles(jnp.asarray(rows.reshape(3, 3, 128))))
    assert tiles.shape == (3, 8, 128)
    flat = tiles.reshape(3, -1).view(np.uint8)  # little-endian words
    assert np.array_equal(flat[:, :384], rows) and not flat[:, 384:].any()


@pytest.mark.parametrize("shape,dtype", [
    ((4, 100), np.uint8),       # not whole 128-byte rows
    ((4, 64), np.uint16),       # neither bytes nor words
    ((4, 0, 128), np.uint8),    # nothing in an item
])
def test_what_is_not_whole_lane_rows_is_refused(shape, dtype):
    x = jnp.zeros(shape, dtype)
    assert not rows_op.tiles_rows(x)
    with pytest.raises(ValueError, match="not whole"):
        rows_op.row_tiles(x)


def test_gather_rows_refuses_tiles_of_another_form():
    tiles = rows_op.row_tiles(jnp.zeros((4, 384), jnp.uint8))
    idx = jnp.zeros(4, jnp.int32)
    with pytest.raises(ValueError, match="row_tiles"):
        rows_op.gather_rows(tiles, idx, width=384, dtype=jnp.float32)
    with pytest.raises(ValueError, match="row_tiles"):
        rows_op.gather_rows(tiles, idx, width=8192, dtype=jnp.uint8)
    with pytest.raises(ValueError, match="not whole"):
        rows_op.gather_rows(tiles, idx, width=100, dtype=jnp.uint8)


@pytest.mark.parametrize("what", ["outputs", "gradient"])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32],
                         ids=["uint8", "float32"])
def test_nature_cnn_reads_batch_last_frames_as_batch_first(dtype, what):
    """The same convolution with other dimension numbers: float32
    round-off, no more."""
    rng = np.random.default_rng(3)
    raw = rng.integers(0, 256, (6, 84, 84, 4)).astype(dtype)
    packed = pack_frames(jnp.asarray(raw))
    last = jnp.moveaxis(packed, 0, -1)
    trunk = NatureCNN(out_dim=32)
    params = trunk.init(jax.random.PRNGKey(0), packed, packed=True)
    weigh = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)

    def scalar(p, x, batch_last):
        return jnp.sum(trunk.apply(p, x, packed=True,
                                   batch_last=batch_last) * weigh)

    if what == "outputs":
        got = trunk.apply(params, last, packed=True, batch_last=True)
        want = trunk.apply(params, packed, packed=True)
        assert got.shape == want.shape == (6, 32)
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
    else:
        got = jax.grad(scalar)(params, last, True)
        want = jax.grad(scalar)(params, packed, False)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.shape == w.shape
            scale = max(float(jnp.max(jnp.abs(w))), 1e-6)
            assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale
    with pytest.raises(ValueError, match="packed"):
        trunk.apply(params, last, batch_last=True)


def test_the_module_tells_batch_last_frames_by_their_shape():
    spec = RLModuleSpec(obs_shape=(84, 84, 4), num_actions=4, conv=True)
    module = spec.build()
    rng = np.random.default_rng(4)
    frames = jnp.asarray(rng.integers(0, 256, (5, 84, 84, 4), dtype=np.uint8))
    actions = jnp.asarray([0, 3, 1, 2, 0])
    params = module.init(jax.random.PRNGKey(2), frames)
    packed = module.pack_obs(frames)
    last = jnp.moveaxis(packed, 0, -1)
    assert last.shape == (*spec.packed_obs_shape, 5)
    forward = jax.jit(module.forward_train)
    for first, end in zip(forward(params, packed, actions),
                          forward(params, last, actions)):
        assert float(jnp.max(jnp.abs(first - end))) < 1e-5
    with pytest.raises(ValueError, match="B last"):
        module.apply(params, last[:, :20])


def test_the_module_packs_raw_frames_into_tiles_and_reads_what_it_packed():
    """``pack_obs_tiled``: the trajectory's tiles and the batch-last frames
    from one kernel; the trunk reads the second as it reads raw frames, and
    a trunk that packs nothing has no such pass."""
    spec = RLModuleSpec(obs_shape=(84, 84, 4), num_actions=4, conv=True)
    assert spec.packs_tiled
    module = spec.build()
    frames = _raw(5, (84, 84), seed=9)
    actions = jnp.asarray([0, 3, 1, 2, 0])
    params = module.init(jax.random.PRNGKey(2), frames)
    tiles, last = module.pack_obs_tiled(frames)
    assert last.shape == (*spec.packed_obs_shape, 5)
    assert np.array_equal(
        np.asarray(tiles),
        np.asarray(rows_op.row_tiles(module.pack_obs(frames))))
    forward = jax.jit(module.forward_train)
    for raw, end in zip(forward(params, frames, actions),
                        forward(params, last, actions)):
        assert float(jnp.max(jnp.abs(raw - end))) < 1e-5
    for other in (RLModuleSpec(obs_shape=(10, 10, 4), num_actions=3,
                               conv=True),       # a board: nothing packed
                  RLModuleSpec(obs_shape=(96, 96, 3), num_actions=3,
                               conv=True),       # three channels: no words
                  RLModuleSpec(obs_dim=4, num_actions=2)):
        assert not other.packs_tiled
