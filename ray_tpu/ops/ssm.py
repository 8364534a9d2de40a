"""The state-space recurrence of a decode step over the slots that are live.

A serve engine holds one ``[heads, head, state]`` float32 state a slot a
mixer layer (``models/falcon_h1.py::Mamba2Mixer``; 4 MiB at the published
widths of both families that run it), and a decode step advances the slots
that are decoding by one token:

    S = exp(dt A) S + (dt x) (x) B_g ,   y = sum_N S C_g

which is ``models/falcon_h1.py::ssd_step``, the recurrence's plain
definition.  The pass is bound by the bytes of state it moves, so
``ssm_step`` moves the live slots' and no other's: the Pallas kernel runs
over a list, made once a step in the same program (``live_slots``), of the
slots that are live; the pool's block is looked up in that list, so the
pipeline copies the listed slots' state from HBM and back, in place, and a
slot that is not listed is neither read nor written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.moe import hit_order

# Bytes of state a grid step works on: a tile of whole heads of one slot.
# A step holds four such tiles (the pool's block coming in and going out,
# each twice over: the pipeline moves the next step's while this one's is
# advanced) and a few temporaries of one head.  One slot's whole state at
# the published widths.
STEP_TILE_BYTES = 4 << 20


def live_slots(active: jax.Array):
    """active [slots] bool → (order [slots] int32, n_live [1] int32): the
    live slots, ascending, in the first ``n_live`` places of ``order`` and
    zeros behind them (``ops/moe.py::hit_order`` over one row)."""
    return hit_order(active[None, :])


def _heads_tile(h: int, g: int, head_bytes: int) -> int:
    """Heads a grid step advances: the most that ``STEP_TILE_BYTES`` hold,
    among the counts that divide the heads and are whole groups or divide
    one (so that a tile's heads read whole rows of B and C)."""
    per_group = h // g
    fits = [t for t in range(1, h + 1)
            if h % t == 0 and (t % per_group == 0 or per_group % t == 0)
            and t * head_bytes <= STEP_TILE_BYTES]
    return max(fits, default=1)


def _ssm_step_kernel(order_ref, n_live_ref,  # SMEM
                     decay_ref, pool_ref, dtx_ref, b_ref, c_ref,
                     out_ref, y_ref, *, heads: int, per_group: int):
    """Grid step (p, j): tile j of the heads of slot ``order[p]``.
    decay_ref [slots * heads] in SMEM; pool_ref / out_ref [tile, P, N], the
    same block of the one pool; dtx_ref / y_ref [P, tile] (a head a lane, so
    that a head's column broadcasts along the state's lanes); b_ref / c_ref
    [groups of the tile, 1, N]."""
    p, j = pl.program_id(0), pl.program_id(1)
    tile = pool_ref.shape[0]
    n_live = n_live_ref[0]

    # An empty list names block (0, last) at every step, which is written
    # back once at the end: as it came.
    @pl.when((n_live == 0) & (p == 0) & (j == 0))
    def _():
        out_ref[...] = pool_ref[...]

    @pl.when(p < n_live)  # past the list: nothing fetched, nothing done
    def _():
        first = order_ref[p] * heads + j * tile
        for i in range(tile):
            group = i // per_group
            state = (decay_ref[first + i] * pool_ref[i]
                     + dtx_ref[:, i:i + 1] * b_ref[group])
            out_ref[i] = state
            y_ref[:, i:i + 1] = jnp.sum(state * c_ref[group], axis=1,
                                        keepdims=True)


def ssm_step(pool: jax.Array, order: jax.Array, n_live: jax.Array,
             x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array):
    """``ssd_step`` on the first ``n_live`` slots of ``order``, in place.
    pool [S, H, P, N] float32 (donate it: the result is the same buffer);
    x [S, H, P]; dt [S, H] float32 (after softplus); a [H] float32; b, c
    [S, G, N].  Returns (the pool, y [S, H, P] float32): a listed slot's
    state advanced by its row and its y; any other slot's state untouched,
    bit for bit, whatever it holds, and its y zero.  Everything in float32.

    The grid is (place in ``order``, tile of heads); the block of the pool,
    and of each row's operands, is looked up in ``order``.  Places at or
    past ``n_live`` name the last real step's block again, which is not
    fetched twice, and compute nothing."""
    _, h, p, n = pool.shape
    return _ssm_step(pool, order, n_live, x, dt, a, b, c,
                     tile=_heads_tile(h, b.shape[1], p * n * 4))


# Jitted on its own so that the layers of a step share one trace and one
# lowering of the kernel: its body is unrolled over a tile's heads, and a
# program lowers again in every process that runs it, cached or not
# (trace and lowering of Nemotron-3-Super's decode program, 5 layers of
# 128 heads, for a described v5e: 8.3 s layer by layer, 3.3 shared, 1.8
# with the fusion; PERF.md, PR 46).
@functools.partial(jax.jit, static_argnames="tile")
def _ssm_step(pool, order, n_live, x, dt, a, b, c, *, tile: int):
    s, h, p, n = pool.shape
    g = b.shape[1]
    f32 = jnp.float32
    tiles, per_group = h // tile, h // g
    groups = max(tile // per_group, 1)  # of B and C a tile reads

    # What is small is made ready outside: the decay a head, and dt x with
    # a head a lane.
    decay = jnp.exp(dt * a).reshape(s * h)
    dtx = (dt[:, :, None] * x.astype(f32)).reshape(s, tiles, tile, p)
    dtx = dtx.transpose(0, 1, 3, 2)
    b, c = (v.astype(f32)[:, :, None, :] for v in (b, c))

    def slot_tile(i, j, order_ref, n_live_ref):
        last = jnp.maximum(n_live_ref[0], 1) - 1
        return (order_ref[jnp.minimum(i, last)],
                jnp.where(i < n_live_ref[0], j, tiles - 1))

    def state_block(i, j, *refs):
        return (*slot_tile(i, j, *refs), 0, 0)

    def group_block(i, j, *refs):
        slot, j = slot_tile(i, j, *refs)
        return slot, j * tile // (per_group * groups), 0, 0

    state_spec = pl.BlockSpec((None, tile, p, n), state_block)
    lanes_spec = pl.BlockSpec((None, None, p, tile), state_block)
    group_spec = pl.BlockSpec((None, groups, 1, n), group_block)
    # Two buffers a block; a head's state, its injection and its product
    # with C beside them.
    vmem = (4 * tile * p * n * 4 + 4 * p * max(tile, 128) * 4
            + 4 * groups * 8 * n * 4 + 4 * p * n * 4)
    pool, y = pl.pallas_call(
        functools.partial(_ssm_step_kernel, heads=h, per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, tiles),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), state_spec,
                      lanes_spec, group_spec, group_spec],
            out_specs=[state_spec, lanes_spec]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, f32),
                   jax.ShapeDtypeStruct((s, tiles, p, tile), f32)],
        # operands count the two prefetched scalars: the pool is the fourth
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20)),
        name="ssm_step",
        interpret=jax.default_backend() == "cpu",
    )(order, n_live, decay, pool, dtx, b, c)
    ids = jnp.arange(s, dtype=jnp.int32)
    listed = jnp.any((order[:, None] == ids[None, :])
                     & (ids[:, None] < n_live[0]), axis=0)
    y = y.transpose(0, 1, 3, 2).reshape(s, h, p)
    return pool, jnp.where(listed[:, None, None], y, 0.0)
