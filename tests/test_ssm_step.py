"""The decode step's state pass over the live slots (``ops/ssm.py``, ISSUE
46), on the CPU with the kernel interpreted, at the tiny widths of both
families that run it.

The yardstick is ``models/falcon_h1.py::ssd_step``, the recurrence's plain
definition over every slot: the kernel gives a listed slot's state and y
within float32 rounding, and leaves any other slot's state bit for bit as
it was, whatever it holds (NaN: nothing of a free slot reaches a live one)
and whatever ``order`` says behind ``n_live``.  Through ``LLMEngine`` (both
families): a slot that was retired keeps its last state through later
steps until an admission overwrites it, and ``stats()`` sums what the
spans say.
"""
import numpy as np
import pytest

from test_decode_lookahead import _drive, _engine, _prompt

SLOTS = 6
SHAPES = {  # heads, head, state, groups; bytes of a grid step's tile
    "falcon_h1_tiny": (4, 16, 8, 2, None),   # one tile: the whole slot
    "nemotron_h_tiny": (4, 16, 8, 1, None),
    "a_tile_in_a_group": (8, 8, 128, 2, 2 * 8 * 128 * 4),  # 2 of 4 heads
    "a_tile_of_two_groups": (8, 8, 128, 4, 4 * 8 * 128 * 4),
}
LIVE = {
    "none": [],
    "one": [3],
    "some": [0, 2, 5],
    "all": list(range(SLOTS)),
}


def _operands(shape, seed=0):
    import jax
    import jax.numpy as jnp

    h, p, n, g, _ = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = jax.random.normal(ks[0], (SLOTS, h, p, n), jnp.float32)
    return pool, (
        jax.random.normal(ks[1], (SLOTS, h, p), jnp.float32),
        jax.nn.softplus(jax.random.normal(ks[2], (SLOTS, h)) - 1.0),
        -jnp.exp(jax.random.normal(ks[3], (h,))),
        jax.random.normal(ks[4], (SLOTS, g, n), jnp.float32),
        jax.random.normal(ks[5], (SLOTS, g, n), jnp.float32))


def _active(live):
    rows = np.zeros((SLOTS,), bool)
    rows[LIVE[live]] = True
    return rows


@pytest.fixture
def step(monkeypatch):
    """``ssm_step`` jitted, its grid step holding the shape's tile."""
    import jax

    from ray_tpu.ops import ssm

    def make(shape):
        tile_bytes = SHAPES[shape][4]
        if tile_bytes is not None:
            monkeypatch.setattr(ssm, "STEP_TILE_BYTES", tile_bytes)
        return jax.jit(ssm.ssm_step)
    return make


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_listed_slots_advance_as_the_recurrence(step, shape, live):
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import ssd_step
    from ray_tpu.ops.ssm import live_slots

    pool, rest = _operands(shape)
    rows = _active(live)
    want_y, want = ssd_step(pool, *rest)
    got, got_y = step(shape)(pool, *live_slots(jnp.asarray(rows)), *rest)
    assert got.shape == pool.shape and got.dtype == jnp.float32
    assert got_y.shape == pool.shape[:3] and got_y.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_y)[rows],
                               np.asarray(want_y)[rows], rtol=1e-5, atol=1e-5)
    assert np.array_equal(_bits(got)[~rows], _bits(pool)[~rows])
    assert not np.asarray(got_y)[~rows].any()


@pytest.mark.parametrize("live", ["none", "one", "some"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_an_unlisted_slot_is_neither_read_nor_written(step, shape, live):
    """Every slot that is not listed holds NaN, in the pool and in its
    row's operands: it comes back bit for bit, its y is zero, and the
    listed slots' results are those of a clean pool."""
    import jax.numpy as jnp

    from ray_tpu.ops.ssm import live_slots

    pool, rest = _operands(shape, seed=1)
    rows = _active(live)
    listed = live_slots(jnp.asarray(rows))
    clean, clean_y = step(shape)(pool, *listed, *rest)
    mask = lambda v: jnp.where(  # noqa: E731
        jnp.asarray(rows).reshape((SLOTS,) + (1,) * (v.ndim - 1)), v, jnp.nan)
    x, dt, a, b, c = rest
    poisoned = mask(pool)
    got, got_y = step(shape)(poisoned, *listed, mask(x), mask(dt), a,
                             mask(b), mask(c))
    assert np.array_equal(_bits(got)[~rows], _bits(poisoned)[~rows])
    assert np.isnan(np.asarray(got)[~rows]).all()
    assert np.array_equal(_bits(got)[rows], _bits(clean)[rows])
    assert np.array_equal(_bits(got_y), _bits(clean_y))
    assert not np.asarray(got_y)[~rows].any()


@pytest.mark.parametrize("shape", ["falcon_h1_tiny", "a_tile_in_a_group"])
@pytest.mark.parametrize("behind", ["a_live_slot", "a_free_slot"])
def test_entries_behind_n_live_change_nothing(step, shape, behind):
    """``order`` past ``n_live`` is not part of the list: whatever stands
    there, the result is that of the list alone."""
    import jax.numpy as jnp

    from ray_tpu.ops.ssm import live_slots

    pool, rest = _operands(shape, seed=2)
    rows = _active("some")
    order, n_live = live_slots(jnp.asarray(rows))
    want, want_y = step(shape)(pool, order, n_live, *rest)
    filler = {"a_live_slot": 2, "a_free_slot": 4}[behind]
    order = jnp.where(jnp.arange(SLOTS) < n_live[0], order, filler)
    got, got_y = step(shape)(pool, order, n_live, *rest)
    assert np.array_equal(_bits(got), _bits(want))
    assert np.array_equal(_bits(got_y), _bits(want_y))


@pytest.mark.parametrize("live", list(LIVE))
def test_the_list_names_the_live_slots_in_ascending_order(live):
    import jax.numpy as jnp

    from ray_tpu.ops.ssm import live_slots

    order, n_live = live_slots(jnp.asarray(_active(live)))
    assert order.shape == (SLOTS,) and order.dtype == jnp.int32
    assert n_live.shape == (1,) and int(n_live[0]) == len(LIVE[live])
    assert np.asarray(order)[:len(LIVE[live])].tolist() == LIVE[live]
    assert not np.asarray(order)[len(LIVE[live]):].any()


@pytest.mark.parametrize("heads,groups,head_bytes,most,want", [
    (32, 2, 128 * 256 * 4, 4 << 20, 32),   # Falcon-H1-34B: the whole slot
    (128, 8, 64 * 128 * 4, 4 << 20, 128),  # Nemotron-3-Super: likewise
    (32, 2, 128 * 256 * 4, 1 << 20, 8),    # half a group
    (128, 8, 64 * 128 * 4, 1 << 20, 32),   # two whole groups
    (12, 3, 1 << 20, 3 << 20, 2),          # 3 does not divide a group of 4
    (4, 2, 8 << 20, 4 << 20, 1),           # a head alone is over: one head
])
def test_a_grid_step_takes_whole_heads_that_read_whole_groups(
        monkeypatch, heads, groups, head_bytes, most, want):
    from ray_tpu.ops import ssm

    monkeypatch.setattr(ssm, "STEP_TILE_BYTES", most)
    assert ssm._heads_tile(heads, groups, head_bytes) == want


# through the engine, both families ----------------------------------------
@pytest.fixture(scope="module", params=["falcon_h1", "nemotron_h"])
def lm(request):
    from ray_tpu.serve.llm_engine import build_model

    return build_model(request.param, {"dtype": "float32"})


def test_a_retired_slot_keeps_its_state_until_admission_overwrites_it(lm):
    """Two requests side by side; the shorter one ends and its slot's
    state stays bit for bit what its last step left, through every later
    step of the other, which goes on advancing; the next admission into
    that slot overwrites it."""
    model, params = lm
    vocab = model.config.vocab_size
    eng = _engine(model, params, max_slots=2)

    def states():
        return [np.asarray(layer["ssm"]) for layer in eng._state]
    try:
        long_ = eng.submit(_prompt(vocab, 9, 80), 16)
        short = eng.submit(_prompt(vocab, 7, 81), 4)
        _drive(eng, [short])
        slot = next(s for s in range(2) if not eng._active[s])
        other = 1 - slot
        assert eng._active[other]
        left = states()
        for _ in range(5):
            eng._iteration(None)
        assert not eng._requests[long_].done.is_set()
        later = states()
        for a, b in zip(left, later):
            assert np.array_equal(_bits(a[slot]), _bits(b[slot]))
            assert not np.array_equal(a[other], b[other])
        again = eng.submit(_prompt(vocab, 5, 82), 3)
        _drive(eng, [again, long_])
        assert any(not np.array_equal(a[slot], b[slot])
                   for a, b in zip(left, states()))
        st = eng.stats()
    finally:
        eng.close()
    assert st["admitted"] == 3


def test_stats_sum_the_slots_the_spans_say_were_moved(lm):
    from ray_tpu import observability as obs
    from ray_tpu.util import tracing

    model, params = lm
    vocab = model.config.vocab_size
    eng = _engine(model, params)
    obs.drain_spans()
    tracing.enable_tracing()
    try:
        assert eng.stats()["state_slots_moved"] == 0
        rids = [eng.submit(_prompt(vocab, n, 90 + n), new)
                for n, new in ((11, 6), (5, 3))]
        _drive(eng, rids)
        st = eng.stats()
    finally:
        tracing.disable_tracing()
        eng.close()
    moved = [s["args"]["state_slots"] for s in obs.drain_spans()
             if s["name"] == "engine.decode.dispatch"]
    assert moved and max(moved) == 2 and min(moved) == 1
    assert st["state_slots_moved"] == sum(moved)
    assert st["state_slots_moved"] <= st["steps"] * eng.max_slots
