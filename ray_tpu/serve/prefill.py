"""Disaggregated prefill: dedicated replicas compute KV, decode adopts.

Prefill and decode have opposite hardware appetites — prefill is one
big compute-bound batch over the whole prompt, decode is thousands of
tiny latency-bound steps — so co-locating them makes every long prompt
a decode stall.  This module splits them (the P/D-disaggregation
design from the serving literature, composed Ray-style over the object
plane): a :class:`PrefillWorker` runs bucketed prefill on its own
replica set, packs the produced KV pages into a wire payload
(``native`` fp32, or ``int8`` block-scaled via the
``ops/collectives`` format from the EQuARX wire, arxiv 2506.17615),
publishes the arrays with ``put_many`` and returns the refs — the same
store-to-store ref chaining the MPMD pipeline ships activations with.
The decode engine (`llm_engine.py`) holds the admitted slot, keeps
decoding its active batch, and adopts the pages with ``get_many`` +
one compiled scatter when the refs resolve.

:class:`PrefillClient` normalizes the three ways a prefill target can
be reached — a serve ``DeploymentHandle`` (autoscaled replica set), a
raw actor handle, or an in-process :class:`PrefillWorker` (tests,
single-host deployments) — behind ``submit()/poll()`` so the engine
loop never blocks on a prompt.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.serve.sampling import SamplingParams

_DEF = object()


def _plane_up() -> bool:
    try:
        import ray_tpu

        return ray_tpu.is_initialized()
    except Exception:
        return False


# ---------------------------------------------------------------------------
# KV page wire format
# ---------------------------------------------------------------------------
def pack_pages(k: np.ndarray, v: np.ndarray,
               wire_dtype: str = "native") -> Dict[str, Any]:
    """Pack [L, n_pages, ps, Hkv, D] K/V page arrays for the wire.

    ``native`` ships fp32 (exact — bf16/f32 caches round-trip
    losslessly, so adopted pages are bit-identical to locally-prefilled
    ones and the token-identity gates hold).  ``int8`` block-scales
    the head_dim axis with the ops/collectives numpy mirror (~3.5-4x
    smaller; approximate, so the engine skips re-publishing such pages
    into the exact prefix cache)."""
    k = np.asarray(k, np.float32)
    v = np.asarray(v, np.float32)
    fp32_bytes = int(k.nbytes + v.nbytes)
    if wire_dtype == "native":
        payload = {"fmt": "native", "k": k, "v": v}
    elif wire_dtype == "int8":
        from ray_tpu.ops.collectives import quantize_block_int8_np

        block = k.shape[-1]
        kq, ks = quantize_block_int8_np(k, block)
        vq, vs = quantize_block_int8_np(v, block)
        payload = {"fmt": "int8", "kq": kq, "ks": ks, "vq": vq, "vs": vs,
                   "block": block, "n": k.shape[-1]}
    else:
        raise ValueError(f"unknown wire_dtype {wire_dtype!r}")
    wire = sum(int(a.nbytes) for a in payload.values()
               if isinstance(a, np.ndarray))
    payload["wire_bytes"] = wire
    payload["fp32_bytes"] = fp32_bytes
    return payload


def unpack_pages(payload: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    if payload["fmt"] == "native":
        return payload["k"], payload["v"]
    from ray_tpu.ops.collectives import dequantize_block_int8_np

    n = int(payload["n"])
    k = dequantize_block_int8_np(payload["kq"], payload["ks"], n)
    v = dequantize_block_int8_np(payload["vq"], payload["vs"], n)
    return k, v


_WIRE_ARRAYS = {"native": ("k", "v"), "int8": ("kq", "ks", "vq", "vs")}


class PrefillWorker:
    """Stateless bucketed-prefill replica.

    One compiled program per power-of-two prompt bucket (the engine's
    prefill bucketing, minus the page scatter — the worker returns the
    raw per-position KV, chopped into pages host-side).  ``prefill``
    also samples the next token with the request's seeded sampler, so
    the decode replica starts from exactly the token a local prefill
    would have produced (replicas share seeded-identical weights).

    Deploy under ``@serve.deployment`` (its own autoscaling config —
    prefill replicas scale on prompt load, decode replicas on decode
    load) or instantiate in-process."""

    def __init__(self, model_kind: str = "gpt2",
                 config_kw: Optional[dict] = None, seed: int = 0,
                 page_size=_DEF, max_ctx: Optional[int] = None,
                 wire_dtype: str = "native",
                 use_object_plane: Optional[bool] = None):
        import jax  # noqa: F401 — fail here, not mid-request

        from ray_tpu.serve.llm_engine import _cfg, build_model

        self._model, self._params = build_model(model_kind, config_kw, seed)
        c = self._model.config
        self.page_size = int(_cfg("serve_page_size", page_size, 16))
        self.max_ctx = int(max_ctx or c.max_position_embeddings)
        self.wire_dtype = wire_dtype
        self._use_plane = use_object_plane
        self.num_layers = c.num_layers
        self.kv_heads = getattr(c, "num_kv_heads", c.num_heads)
        self.head_dim = c.head_dim
        self.dtype = c.dtype
        self._fns: Dict[int, Any] = {}
        self._stats = {"requests": 0, "tokens": 0, "wire_bytes": 0,
                       "fp32_bytes": 0}

    def _bucket_for(self, p: int) -> int:
        b = 8
        while b < p:
            b <<= 1
        return min(b, self.max_ctx)

    def _fn(self, bucket: int):
        fn = self._fns.get(bucket)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from ray_tpu.serve.llm_engine import _attend_uncached
        from ray_tpu.serve.sampling import sample_tokens_with_logprobs

        model, L = self._model, self.num_layers

        def prefill(params, tokens, p, temp, top_p, seed):
            ids = tokens[None]
            positions = jnp.arange(bucket)[None]
            logits, new_kvs = model.apply(
                {"params": params}, ids, positions, [_attend_uncached] * L)
            toks, logps = sample_tokens_with_logprobs(
                logits[0, p - 1][None], jnp.reshape(p, (1,)),
                jnp.reshape(temp, (1,)), jnp.reshape(top_p, (1,)),
                jnp.reshape(seed, (1,)))
            newk = jnp.stack([nk[0][0] for nk in new_kvs])  # [L,bkt,Hkv,D]
            newv = jnp.stack([nk[1][0] for nk in new_kvs])
            return newk, newv, toks[0], logps[0]

        fn = jax.jit(prefill)
        self._fns[bucket] = fn
        return fn

    def prefill(self, tokens, start: int = 0, temperature: float = 0.0,
                top_p: float = 1.0, seed: int = 0) -> Dict[str, Any]:
        """Compute KV for ``tokens`` and return the pages covering
        positions ``[start, len(tokens))`` (``start`` is the decode
        side's cached-prefix length, page-aligned — attention needs the
        whole prompt, the wire only the uncached tail) plus the sampled
        next token.  With a connected object plane the page arrays ride
        ``put_many`` and the return value carries refs."""
        tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
        p = len(tokens)
        if not p:
            raise ValueError("empty prompt")
        if start % self.page_size:
            raise ValueError(f"start {start} is not page-aligned "
                             f"(page_size {self.page_size})")
        bucket = self._bucket_for(p)
        toks = np.zeros((bucket,), np.int32)
        toks[:p] = tokens
        newk, newv, nxt, nxt_logp = self._fn(bucket)(
            self._params, toks, np.int32(p), np.float32(temperature),
            np.float32(top_p), np.int32(seed))
        ps = self.page_size
        n0, n1 = start // ps, math.ceil(p / ps)
        buf_shape = (self.num_layers, n1 * ps, self.kv_heads, self.head_dim)
        bk = np.zeros(buf_shape, np.float32)
        bv = np.zeros(buf_shape, np.float32)
        bk[:, :p] = np.asarray(newk, np.float32)[:, :p]
        bv[:, :p] = np.asarray(newv, np.float32)[:, :p]
        pk = bk.reshape(self.num_layers, n1, ps, self.kv_heads,
                        self.head_dim)[:, n0:]
        pv = bv.reshape(self.num_layers, n1, ps, self.kv_heads,
                        self.head_dim)[:, n0:]
        payload = pack_pages(pk, pv, self.wire_dtype)
        payload.update(next_token=int(nxt), next_logp=float(nxt_logp),
                       p=p, start=start)
        self._stats["requests"] += 1
        self._stats["tokens"] += p - start
        self._stats["wire_bytes"] += payload["wire_bytes"]
        self._stats["fp32_bytes"] += payload["fp32_bytes"]
        use_plane = self._use_plane if self._use_plane is not None \
            else _plane_up()
        if use_plane:
            import ray_tpu

            names = _WIRE_ARRAYS[payload["fmt"]]
            refs = ray_tpu.put_many([payload.pop(n) for n in names])
            payload["refs"] = refs
            payload["ref_names"] = list(names)
        return payload

    def prefill_many(self, requests: List[dict]) -> List[Dict[str, Any]]:
        """Batched entry point (one RPC, one coalesced ``put_many`` ride
        per request): each request is the kwargs of :meth:`prefill`."""
        return [self.prefill(**r) for r in requests]

    def stats(self) -> Dict[str, int]:
        out = dict(self._stats)
        out["buckets"] = len(self._fns)
        return out

    def drain(self) -> bool:
        return True


# ---------------------------------------------------------------------------
# Client side (lives inside the decode engine's loop)
# ---------------------------------------------------------------------------
class _PrefillJob:
    """One in-flight prefill.  ``poll()`` returns None while pending,
    else ``(k, v, next_token, meta)`` with [L, n_pages, ps, Hkv, D]
    float32 page arrays; raises the remote error, typed."""

    def __init__(self, future=None, payload=None):
        self._future = future
        self._payload = payload
        self._delivered = False

    def poll(self):
        if self._delivered:
            return None
        if self._payload is None:
            if self._future is None or not self._future.done():
                return None
            self._payload = self._future.result()
        self._delivered = True
        return _resolve_payload(self._payload)


def _resolve_payload(payload: Dict[str, Any]):
    payload = dict(payload)
    refs = payload.pop("refs", None)
    if refs is not None:
        import ray_tpu

        vals = ray_tpu.get_many(list(refs))
        payload.update(zip(payload.pop("ref_names"), vals))
    k, v = unpack_pages(payload)
    meta = {"wire_bytes": payload["wire_bytes"],
            "fp32_bytes": payload["fp32_bytes"],
            "exact": payload["fmt"] == "native",
            "next_logp": payload.get("next_logp", float("nan"))}
    return k, v, payload["next_token"], meta


class PrefillClient:
    """Engine-facing adapter over a prefill target: a serve
    DeploymentHandle (``.method``), an actor handle (``.prefill.remote``)
    or an in-process PrefillWorker.  A local worker runs on a
    background thread (jit dispatch releases the GIL into XLA), so even
    single-process disaggregation overlaps prefill with the engine's
    decode loop — the whole point of the split."""

    def __init__(self, target):
        self._target = target
        self._pool = None
        if hasattr(target, "method"):
            self._kind = "deployment"
        elif hasattr(getattr(target, "prefill", None), "remote"):
            self._kind = "actor"
        elif callable(getattr(target, "prefill", None)):
            self._kind = "local"
        else:
            raise TypeError(
                f"not a prefill target: {type(target).__name__} (need a "
                "DeploymentHandle, an actor handle, or a PrefillWorker)")

    def submit(self, tokens, start: int,
               sampling: SamplingParams) -> _PrefillJob:
        args = (list(tokens), int(start), float(sampling.temperature),
                float(sampling.top_p), int(sampling.seed))
        if self._kind == "deployment":
            ref = self._target.method("prefill").remote(*args)
            return _PrefillJob(future=ref.future())
        if self._kind == "actor":
            ref = self._target.prefill.remote(*args)
            return _PrefillJob(future=ref.future())
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="rtpu-prefill")
        return _PrefillJob(
            future=self._pool.submit(self._target.prefill, *args))


def as_prefill_client(target) -> PrefillClient:
    return target if isinstance(target, PrefillClient) \
        else PrefillClient(target)
