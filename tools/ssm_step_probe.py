"""The decode step's state pass alone, on the chip: ``ops/ssm.py::ssm_step``
(the kernel over the live slots, in place) beside the recurrence's plain
definition as a decode program ran it before (``ssd_step`` over every slot,
a free slot's state written back as it was), on one layer's pool of 48
slots at Falcon-H1-34B's and Nemotron-3-Super's widths.

    chiprun -- python tools/ssm_step_probe.py [--tile-mib 1 2 4]

One JSON line a case: ``ms`` a pass (the mean of ``--steps`` passes inside
one program, the pool carried from pass to pass), the share of the chip's
HBM peak that the live slots' bytes make of it, the largest relative error
of the kernel's state and y against the plain form on the same operands,
and whether the slots not listed (filled with NaN) came back bit for bit.
The last line says whether every case was sound.  Times come from a chip
only: on the CPU the kernel is interpreted and the line says so.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.models.falcon_h1 import ssd_step  # noqa: E402
from ray_tpu.ops import ssm  # noqa: E402

SHAPES = {  # slots, heads, head, state, groups
    "falcon_h1_34b": (48, 32, 128, 256, 2),
    "nemotron3_super": (48, 128, 64, 128, 8),
    "tiny": (6, 4, 16, 8, 2),
}


def _plain(pool, active, x, dt, a, b, c):
    y, new = ssd_step(pool, x, dt, a, b, c)
    return jnp.where(active[:, None, None, None], new, pool), y


def _kernel(pool, active, x, dt, a, b, c):
    return ssm.ssm_step(pool, *ssm.live_slots(active), x, dt, a, b, c)


def _timed(form, steps):
    def run(pool, active, *rest):
        def body(_, carry):
            pool, acc = carry
            pool, y = form(pool, active, *rest)
            return pool, acc + y
        return jax.lax.fori_loop(
            0, steps, body, (pool, jnp.zeros(pool.shape[:3], jnp.float32)))
    return jax.jit(run, donate_argnums=0)


def case(name, live, steps, seed):
    s, h, p, n, g = SHAPES[name]
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    active = np.zeros((s,), bool)
    active[np.asarray(jax.random.permutation(ks[6], s))[:live]] = True
    active = jnp.asarray(active)
    pool = jnp.where(active[:, None, None, None],
                     jax.random.normal(ks[0], (s, h, p, n), jnp.float32),
                     jnp.nan)
    rest = (jax.random.normal(ks[1], (s, h, p), jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(ks[2], (s, h)) - 3.0),
            -jnp.exp(jax.random.normal(ks[3], (h,))),
            jax.random.normal(ks[4], (s, g, n), jnp.bfloat16),
            jax.random.normal(ks[5], (s, g, n), jnp.bfloat16))
    out = {"shape": name, "live": live, "slots": s,
           "tile_heads": ssm._heads_tile(h, g, p * n * 4)}
    # one pass of each form on the same operands
    want_pool, want_y = jax.jit(_plain)(pool, active, *rest)
    got_pool, got_y = jax.jit(_kernel)(pool, active, *rest)
    rows = np.asarray(active)
    rel = lambda got, want: float(  # noqa: E731
        np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    if live:
        out["state_err"] = rel(np.asarray(got_pool)[rows],
                               np.asarray(want_pool)[rows])
        out["y_err"] = rel(np.asarray(got_y)[rows], np.asarray(want_y)[rows])
    out["unlisted_bit_for_bit"] = bool(np.array_equal(
        np.asarray(got_pool)[~rows].view(np.uint32),
        np.asarray(pool)[~rows].view(np.uint32)))
    out["unlisted_y_zero"] = not np.asarray(got_y)[~rows].any()
    out["sound"] = (out["unlisted_bit_for_bit"] and out["unlisted_y_zero"]
                    and out.get("state_err", 0) < 1e-5
                    and out.get("y_err", 0) < 1e-5)
    del want_pool, got_pool
    moved = 2 * live * h * p * n * 4
    for label, form in (("plain", _plain), ("kernel", _kernel)):
        fn = _timed(form, steps)
        held, acc = fn(pool + 0.0, active, *rest)  # compiles
        acc.block_until_ready()
        t0 = time.perf_counter()
        held, acc = fn(held, active, *rest)
        acc.block_until_ready()
        out[label + "_ms"] = (time.perf_counter() - t0) / steps * 1e3
        out[label + "_live_gb_s"] = moved / out[label + "_ms"] / 1e6
        del held, acc
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=None)
    ap.add_argument("--live", nargs="+", type=int, default=None)
    ap.add_argument("--tile-mib", nargs="+", type=float, default=[None])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    shapes = args.shapes or (["falcon_h1_34b", "nemotron3_super"] if on_chip
                             else ["tiny"])
    sound = True
    for tile in args.tile_mib:
        if tile is not None:
            ssm.STEP_TILE_BYTES = int(tile * (1 << 20))
        for name in shapes:
            s = SHAPES[name][0]
            for live in args.live or [0, 1, s // 4, s // 2, 3 * s // 4, s]:
                out = case(name, live, args.steps, args.seed)
                out["tile_mib"] = ssm.STEP_TILE_BYTES / (1 << 20)
                sound &= out["sound"]
                print(json.dumps(out), flush=True)
    print(json.dumps({"ok": bool(sound), "device": jax.devices()[0].device_kind,
                      "timed_on_chip": on_chip}))
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
