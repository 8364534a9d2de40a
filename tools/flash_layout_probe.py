"""The flash kernels' operand layouts side by side, on the chip: one
attention layer's forward and backward as a function of the fused
projection ``qkv [B, L, 3 * H * D]`` (what GPT-2's block holds), bf16
causal, by every way into the kernels that ``ops/attention.py`` has:

    head_major   q, k, v split off, ``[B * H, L, D]`` (a transpose each way)
    columns      q, k, v split off, read as column blocks of ``[B, L, H * D]``
    fused        read out of qkv where it lies, the backward writing one dqkv

    chiprun -- python tools/flash_layout_probe.py [--shapes 1]

One JSON line a shape: ``ms`` a call of each form (forward and backward, the
mean of ``--calls`` calls one after the other, the last one waited for),
``kernels_ms`` of the first two on three arrays of their own (no split, no
concatenation: what ``mha_attention(q, k, v)`` costs), which form the shape
rule gives the shape, and the largest error of each form's output and dqkv
against ``head_major`` relative to the largest value.  The last line says
whether every form agreed.  Times come from a chip only: on the CPU the
kernels are interpreted (``--tiny``) and the line says so.

``--chunks``: also ``chunk_ms``, the form the shape rule gives (``fused``
where there is one) with a masked tile multiplied whole (``0``, as until PR
54) and cut into chunks of 256 and 128 columns (``attention._CHUNK``):
``fwd`` the forward alone, ``both`` forward and backward, and the error of
each against the uncut tile.
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

from ray_tpu.ops import attention  # noqa: E402

SHAPES = [  # batch, length, heads, head
    (8, 1024, 16, 64),   # the train cells': a pair of heads a block
    (8, 1024, 8, 128),   # Llama-family heads: a head a block
    (4, 2048, 8, 128),   # ... at the longest the whole-head kernels hold
    (8, 1024, 32, 32),   # four heads a block
    (2, 4096, 8, 128),   # the rolled, two-kernel form (head-major)
]
TINY = [(1, 256, 4, 64), (1, 256, 2, 128)]


def _forms(h, interpret):
    def three(q, k, v):
        b, l, c = q.shape
        q, k, v = (x.reshape(b, l, h, c // h) for x in (q, k, v))
        return attention.flash_attention(
            q, k, v, causal=True, interpret=interpret).reshape(b, l, c)

    def split(qkv):
        return three(*jnp.split(qkv, 3, axis=-1))

    def fused(qkv):
        return attention.flash_attention_qkv(qkv, h, causal=True,
                                             interpret=interpret)
    return three, split, fused


def _grad(form):
    """Forward and backward in one program, the cotangent an operand."""
    def run(w, *ops):
        out, vjp = jax.vjp(form, *ops)
        return (out,) + vjp(w)
    return jax.jit(run)


def _ms(fn, args, calls):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def case(shape, calls, seed, interpret, chunks=False):
    b, l, h, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    qkv = jax.random.normal(keys[0], (b, l, 3 * h * d), jnp.bfloat16)
    w = jax.random.normal(keys[1], (b, l, h * d), jnp.bfloat16)
    parts = tuple(jnp.split(qkv, 3, axis=-1))
    three, split, fused = _forms(h, interpret)
    rule = attention._heads_per_block
    blocks = attention._auto_blocks(l, l, d, True)
    ms, kernels_ms, results = {}, {}, {}
    for name, heads_of in (("head_major", lambda *a: 0), ("columns", rule)):
        attention._heads_per_block = heads_of
        # the rule is read while tracing: fresh programs for each setting
        jax.clear_caches()
        by_qkv, by_parts = _grad(split), _grad(three)
        ms[name] = _ms(by_qkv, (w, qkv), calls)
        kernels_ms[name] = _ms(by_parts, (w,) + parts, calls)
        results[name] = by_qkv(w, qkv)
    by_qkv = _grad(fused)
    ms["fused"] = _ms(by_qkv, (w, qkv), calls)
    results["fused"] = by_qkv(w, qkv)

    def err(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        return float(np.abs(got - want).max() / np.abs(want).max())

    errs = {name: {"out": err(r[0], results["head_major"][0]),
                   "dqkv": err(r[1], results["head_major"][1])}
            for name, r in results.items() if name != "head_major"}
    line = {"shape": list(shape), "blocks": list(blocks),
            "heads_per_block": rule(l, l, h, d, 2, *blocks, True),
            "ms": ms, "kernels_ms": kernels_ms,
            "rel_err_vs_head_major": errs}
    if chunks:
        line["chunk_ms"], chunk_errs = _chunk_case(fused, (w, qkv), calls, err)
        errs = dict(errs, **chunk_errs)
        line["rel_err_vs_uncut"] = chunk_errs
    # bf16: p and ds are rounded to 8 bits before their matmuls
    line["ok"] = all(e <= 2 ** -6 for form in errs.values()
                     for e in form.values())
    return line


def _chunk_case(form, args, calls, err):
    """({columns a chunk: {"fwd", "both"} ms}, {columns: errors against 0})."""
    chunk = attention._CHUNK
    ms, results = {}, {}
    for g in (0, 256, 128):
        attention._CHUNK = g or 1 << 30  # wider than any tile: never cut
        jax.clear_caches()  # the constant is read while tracing
        forward, both = jax.jit(form), _grad(form)
        ms[str(g)] = {"fwd": _ms(forward, args[1:], calls),
                      "both": _ms(both, args, calls)}
        results[g] = both(*args)
    attention._CHUNK = chunk
    jax.clear_caches()
    return ms, {str(g): {"out": err(r[0], results[0][0]),
                         "dqkv": err(r[1], results[0][1])}
                for g, r in results.items() if g}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shapes", type=int, default=len(SHAPES),
                    help="only the first so many shapes")
    ap.add_argument("--chunks", action="store_true",
                    help="also the rule's form by the columns of a masked "
                         "tile's chunk (0: multiplied whole)")
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes, the kernels interpreted (the CPU)")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print(f"no TPU here ({device.platform}): --tiny rehearses the "
              "control flow; times come from a chip", file=sys.stderr)
        return 2
    ok = True
    for shape in TINY if args.tiny else SHAPES[:args.shapes]:
        line = case(shape, 2 if args.tiny else args.calls, args.seed,
                    interpret=args.tiny, chunks=args.chunks)
        line["device"] = {"platform": device.platform,
                          "kind": device.device_kind}
        line["interpreted"] = args.tiny
        ok = ok and line["ok"]
        print(json.dumps(line), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
