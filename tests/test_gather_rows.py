"""``ops/gather_rows.py``: selected rows of a sample-major buffer handed
back batch-minor (``rows[idx].T``), and the same pass the other way
(``tile_columns``), both in interpret mode against plain ``jax.numpy``; and
``NatureCNN`` on what the kernel hands over, packed frames with the batch
last, against the same frames batch-first."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.nature_cnn import NatureCNN, pack_frames
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
