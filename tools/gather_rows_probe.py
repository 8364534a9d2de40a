"""Anakin PPO's frame path alone, on the chip: a minibatch of packed frames
out of a sample-major trajectory and into ``Conv_0``'s forward pass and
weight gradient, by the two ways ``make_anakin_ppo`` has:

    xla       ``v[idx]`` out of ``u8[S, 242, 128]``, frames ``[B, 22, 22, 64]``
    rows_dma  ``ops.gather_rows`` out of word tiles ``u32[S, 64, 128]``,
              frames ``[22, 22, 64, B]``, the batch last

    chiprun -- python tools/gather_rows_probe.py [--blocks 128,256,512]

The trajectory is made as a rollout makes it, 2,048 frames a step: kept
batch-first for ``xla``, through ``ops.gather_rows.tile_columns`` for
``rows_dma``.  One JSON line says whether the kernels' output is the plain
gather's, byte for byte, and whether the convolution's output and gradient
agree; then a line a form and block size with ``ms`` a call (the mean of
``--calls`` calls one after the other, the last one waited for) of the gather
alone, of gather + ``Conv_0`` forward + weight gradient, and of a step's
``tile_columns``; then, from a profile of a few calls, the device's
operations by name.  **A ``copy`` of 8,192 frames between the kernel and
either ``Conv_0`` fusion means nothing was gained** (ISSUE 58).

Then a rollout step's side (``"form": "fold_tiles"``): raw frames
``u8[2048, 84, 84, 4]`` carried through a loop as the environment's frame
stack carries them (shifted a channel a step, so the compiler lays them out
as it does there), into the trajectory's slab and batch-minor for the
trunk, by ``fold_tiles`` (one kernel) and by ``pack_frames`` +
``tile_columns`` (the compiler's four passes and a kernel): whether the two
slabs are equal byte for byte, ``ms`` a step of the whole loop, and from a
profile the kernel's own ``ms`` a step, the ``kernel_gb_s`` of the bytes a
step needs moved (raw frames read once, both results written once) and the
device's operations of a step by name.  **A ``copy``, a
``pad`` or a ``transpose`` of 2,048 frames beside the kernel means a
relayout stayed in front of it** (ISSUE 60).  Times come from a chip only:
on the CPU the kernels are interpreted (``--tiny``) and the lines say so.
"""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.models.nature_cnn import (PackedConv, pack_frames,  # noqa: E402
                                       pack_frames_tiled)
from ray_tpu.ops import gather_rows as rows_op  # noqa: E402

FRAME = (22, 22, 64)  # a packed 84x84x4 frame
WIDTH = 22 * 22 * 64


def _trajectory(steps, envs, seed):
    """Both forms of ``steps * envs`` random frames, a step at a time."""
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(first, tiles, t, key):
        frames = jax.random.bits(key, (envs, *FRAME), jnp.uint8)
        at = (t * envs, 0, 0)
        return (jax.lax.dynamic_update_slice(
                    first, frames.reshape(envs, -1, 128), at),
                jax.lax.dynamic_update_slice(
                    tiles, rows_op.tile_columns(frames.reshape(envs, -1).T),
                    at))

    first = jnp.zeros((steps * envs, WIDTH // 128, 128), jnp.uint8)
    tiles = jnp.zeros((steps * envs, 64, 128), jnp.uint32)
    for t, key in enumerate(jax.random.split(jax.random.PRNGKey(seed),
                                             steps)):
        first, tiles = step(first, tiles, t, key)
    return first, tiles


def _timed(fn, *args, calls):
    out = jax.block_until_ready(fn(*args))  # compiles
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def _rollout(fold: bool, steps: int, envs: int):
    """``steps`` rollout steps' frame path alone, as one program: the frame
    stack shifted a channel (the env's part), then packed into the slab and
    batch-minor, the second summed so that it is read."""
    def step(t, carry):
        obs, tiles, seen_sum = carry
        obs = jnp.concatenate([obs[..., 1:], obs[..., :1] + 1], axis=-1)
        if fold:
            tiles, seen = pack_frames_tiled(obs, into=tiles, at=t * envs)
            seen = seen.reshape(-1, envs)
        else:
            seen = jax.lax.optimization_barrier(pack_frames(obs))
            seen = seen.reshape(envs, -1).T
            tiles = rows_op.tile_columns(seen, into=tiles, at=t * envs)
        return obs, tiles, seen_sum + jnp.sum(seen[::128], dtype=jnp.int32)

    @jax.jit
    def run(obs):
        tiles = rows_op.empty_tiles(steps * envs, WIDTH, jnp.uint8)
        return jax.lax.fori_loop(0, steps, step,
                                 (obs, tiles, jnp.int32(0)))[1:]

    return run


def fold_case(args, on_chip: bool) -> bool:
    """The rollout step's side: ``fold_tiles`` against ``pack_frames`` +
    ``tile_columns``."""
    steps, envs = (2, 8) if args.tiny else (16, 2048)
    obs = jax.random.bits(jax.random.PRNGKey(args.seed + 3),
                          (envs, 84, 84, 4), jnp.uint8)
    # raw frames read (84 bytes a row), the slab and the batch-minor bytes
    moved = envs * (84 * 84 * 4 + 64 * 128 * 4 + WIDTH)
    calls = 1 if args.tiny else max(args.calls // 4, 2)

    def line(name, run, want=None):
        tiles, seen = jax.block_until_ready(run(obs))
        out = {"form": name,
               "ms_a_step": _timed(run, obs, calls=calls) / steps}
        if want is not None:
            out["slabs_equal"] = bool(jnp.array_equal(tiles, want[0])) \
                and int(seen) == int(want[1])
        if on_chip:  # the kernel's own time, and what stands beside it
            from benchmark import common

            window = common.TracedWindow("fold_tiles_probe")
            jax.block_until_ready(run(obs))
            ops = sorted(window.close().get("op_s", {}).items(),
                         key=lambda kv: -kv[1])
            kernel = sum(v for k, v in ops if k.startswith("tpu_custom_call"))
            out["kernel_ms_a_step"] = kernel / steps * 1e3
            if want is not None:
                out["kernel_gb_s"] = moved * steps / kernel / 1e9
            out["ops_ms_a_step"] = {k: round(v / steps * 1e3, 4)
                                    for k, v in ops[:10]}
        print(json.dumps(out))
        return tiles, seen, out.get("slabs_equal", True)

    *want, _ = line("pack_frames+tile_columns", _rollout(False, steps, envs))
    return line("fold_tiles", _rollout(True, steps, envs), want)[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--blocks", default="128,256,512")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.tiny:
        print("no TPU: --tiny rehearses the control flow", file=sys.stderr)
        return 1
    steps, envs, batch = (2, 16, 24) if args.tiny else (64, 2048, 8192)
    calls = 1 if args.tiny else args.calls
    where = {"device": jax.devices()[0].device_kind,
             "times": "chip" if on_chip else "cpu, interpreted: not times"}

    first, tiles = _trajectory(steps, envs, args.seed)
    total = steps * envs
    idx = jax.random.permutation(jax.random.PRNGKey(args.seed + 1),
                                 total)[:batch]
    idx = idx.at[0].set(total - 1).at[1].set(0).at[2].set(0)  # ends, a repeat
    conv = PackedConv(32)
    params = conv.init(jax.random.PRNGKey(2), jnp.zeros((1, *FRAME)))

    def loss(p, frames, batch_last):
        x = jax.lax.optimization_barrier(frames).astype(jnp.float32) / 255.0
        y = conv.apply(p, x, batch_last=batch_last)
        return jnp.sum(jnp.maximum(y, 0.0) ** 2) / y.size

    def gather_xla(buf, i):
        return buf[i].reshape(-1, *FRAME)

    def gather_dma(buf, i):
        return rows_op.gather_rows(buf, i, width=WIDTH,
                                   dtype=jnp.uint8).reshape(*FRAME, -1)

    forms = {"xla": (gather_xla, first, False),
             "rows_dma": (gather_dma, tiles, True)}

    def both(name):
        gather, buf, last = forms[name]
        alone = jax.jit(gather)
        through = jax.jit(lambda p, b, i: jax.value_and_grad(loss)(
            p, gather(b, i), last))
        return alone, through, buf

    # what the kernels hand over against the plain gather
    want = np.asarray(jax.jit(gather_xla)(first, idx[:256]))
    got = np.asarray(jax.jit(gather_dma)(tiles, idx[:256]))
    same = bool(np.array_equal(np.moveaxis(got, -1, 0), want))
    (lx, gx), (ld, gd) = (both(n)[1](params, both(n)[2], idx) for n in forms)
    err = float(jnp.max(jnp.abs(gx["params"]["kernel"]
                                - gd["params"]["kernel"])))
    print(json.dumps({**where, "frames_equal": same,
                      "loss": [float(lx), float(ld)], "grad_max_err": err}))
    ok = same and abs(float(lx) - float(ld)) < 1e-4 and err < 1e-4

    def line(name, block=None):
        alone, through, buf = both(name)
        out = {"form": name, "block": block,
               "gather_ms": _timed(alone, buf, idx, calls=calls),
               "gather_conv0_ms": _timed(through, params, buf, idx,
                                         calls=calls)}
        if name == "rows_dma":
            cols = first[:envs].reshape(envs, -1).T
            out["tile_columns_ms"] = _timed(
                jax.jit(rows_op.tile_columns), cols, calls=calls)
        print(json.dumps(out))
        return through, buf

    line("xla")
    for block in (int(b) for b in args.blocks.split(",")):
        rows_op.BLOCK = block
        jax.clear_caches()
        through, buf = line("rows_dma", block)

    if on_chip:  # the last block size's operations, and the plain path's
        from benchmark import common

        xla_through, xla_buf = both("xla")[1:]
        jax.block_until_ready(xla_through(params, xla_buf, idx))
        window = common.TracedWindow("gather_rows_probe")
        for _ in range(4):
            out = through(params, buf, idx)
            out = xla_through(params, xla_buf, idx)
        jax.block_until_ready(out)
        ops = window.close().get("op_s", {})
        print(json.dumps({"ops_ms_a_call": {
            k: round(v / 4 * 1e3, 3) for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1])[:12]}}))
    ok = fold_case(args, on_chip) and ok
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
