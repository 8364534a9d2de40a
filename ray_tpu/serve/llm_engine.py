"""Continuous-batching LLM decode engine with a paged KV cache.

The inference half of the north star: `ray_tpu/serve/` routed and
wall-clock-batched requests, but had no decode path — this module is the
replica-resident engine that turns the models we train
(`ray_tpu/models/gpt2.py`, `llama.py`) into a serving workload
(reference composition: Ray's latency-oriented serving tier over the
task/actor/object substrate, arxiv 1712.05889; engine design follows the
continuous-batching literature — Orca's iteration-level scheduling and
vLLM's paged KV cache).

Load-bearing ideas:

1. **Fixed-slot compiled decode step.**  The decode program is compiled
   ONCE for `[max_slots]`-shaped inputs (token ids, lengths, page table,
   active mask, sampling params).  Admitting or retiring a request flips
   host-side state — it never changes a traced shape, so the
   steady-state loop never recompiles.  Prefill compiles per
   power-of-two prompt bucket (bounded: log2(max_ctx) programs).

2. **Token-boundary admission.**  The engine loop runs one decode step
   for ALL in-flight requests, then admits pending requests into free
   slots *between* steps (one prefill each) — a new request joins the
   running batch at the next token boundary instead of waiting for the
   batch to drain (Orca's iteration-level scheduling).  The plain decode
   loop keeps one step in flight: step n+1 is dispatched from step n's
   tokens where they are, on the device, and the host reads and emits
   step n, admits and grows pages while a program runs
   (``_decode_once``).

3. **Paged KV cache.**  K/V live in fixed-size pages allocated from a
   device-resident pool (`PagePool` — the SegmentPool free-list recycle
   design from `_private/object_store.py:163`, collapsed to one size
   class because pages are uniform).  A sequence owns `ceil(len/page)`
   pages found through a per-slot page table; the decode step's
   attention reads them where they lie (``ops/paged_attention.py``: no
   dense view of the cache is built) and the new token's K/V is
   scattered back in place.
   Long and short sequences share the pool without fragmentation, pages
   recycle at retirement, and when the pool runs dry the engine preempts
   the youngest request (its pages free; it restarts later from
   prompt+generated-so-far — decode is seed-deterministic, so resumed
   output is identical and already-streamed chunks are never re-sent).

4. **Seeded sampling** (`serve/sampling.py`).  Temperature/top-p with a
   per-request seed; the token at absolute position t is always drawn
   with ``fold_in(PRNGKey(seed), t)``, so outputs are bitwise
   reproducible across runs, schedules, preemption-resume, and the
   speculative verify step.  ``temperature=0`` (default) is greedy
   argmax — the token-identity contract with the uncached reference.

5. **Speculative decoding.**  With a tiny ``draft_model``, each
   iteration runs ``spec_tokens-1`` cheap draft steps proposing tokens,
   then ONE target verify step over the `[max_slots, spec_tokens]`
   window that samples the target's token at every position
   (accept-longest-prefix).  Because sampling is position-seeded, the
   accepted stream is *bitwise* the non-speculative stream — the draft
   only changes how many tokens each target step yields.  The draft
   shares the page table (its pages are a parallel set of arrays), so
   page accounting stays single-pool.

6. **Cluster-wide prefix cache** (`serve/prefix_cache.py`).  After
   prefill, every full page's K/V is content-addressed by the blake2b
   of the token prefix that produced it, kept in a host LRU, and
   (optionally) published to the object plane via ``put_many`` +
   registered in a shared PrefixDirectory actor.  Admission looks up
   the longest cached prefix and prefills only the uncached tail
   (a cache-aware "tail prefill" program per bucket).

7. **Disaggregated prefill** (`serve/prefill.py`).  With a
   ``prefill=`` client, admissions with a long uncached tail are
   offloaded to dedicated prefill replicas: the engine reserves the
   slot + pages, the remote worker computes the tail KV and streams the
   pages back as object-plane refs (optionally int8 block-scaled via
   ``ops/collectives``), and the engine adopts them at a later token
   boundary — decode never stalls on a long prompt.

8. **Token-boundary hot weight swap** (``swap_weights``).  The RLHF
   close-the-loop primitive: new params install *between* decode steps
   — one ``device_put`` per version (params are a plain argument of
   the compiled steps, so a swap never recompiles and
   ``decode_cache_size`` stays 1), zero in-flight requests dropped.
   In-flight slots are recycled through the recompute-preemption path
   so their KV is rebuilt under the NEW weights (their already-sampled
   tokens are data and survive verbatim), every emitted token is
   stamped with the weight version it was sampled under, and the
   prefix-cache namespace folds the version in
   (``prefix_cache.versioned_namespace``) so stale pages become
   unaddressable.  Each decode/prefill step also captures the sampled
   token's **behavior logprob** (raw log-softmax — see
   ``sampling.sample_tokens_with_logprobs``), so the generation that
   serves RLHF rollouts yields the exact PPO-ratio denominator with no
   second forward pass (``rollout()`` / ``generate_rollouts``).  With
   ``record_experts=True`` the decode program and the full prefills of a
   routed model also return what each row's routers chose, and a request
   that asks (``submit(record_experts=True)``) gets them with its
   rollout, one [expert layers, k] a row fed: for a learner that replays
   the routing it sampled under, and for a reference that is given the
   program's choices.

9. **Per-slot recurrent state** beside the pages.  A model whose layers
   carry a state of fixed size from token to token (a state-space mixer:
   ``models/falcon_h1.py``) names it in ``slot_state``; the engine then
   keeps one array a layer a kind, ``[max_slots, ...]``, on the pools'
   device and donates them through the decode and prefill programs.  A
   model whose layers differ in kind (``models/nemotron_h.py``,
   ``models/ling_linear.py``) says how many write K/V and how many carry
   state (``kv_layers``, ``state_layers``): the pool and the state list
   have that many; what a pool row holds is the config's ``num_kv_heads``
   x ``head_dim``, which a model with a latent cache states as 1 x the
   latent row's width.  A
   prefill writes its slot's state as it stands after the prompt's last
   real row (the bucket's padding advances nothing), so admission is the
   reset and recompute-preemption rebuilds it; a decode step touches the
   live slots only (``ops/ssm.py``: the state pass follows the step's own
   list of them and updates the pool in place, so a free slot's state is
   neither read nor written, and a retired slot keeps its last state
   until admission overwrites it; ``stats()["state_slots_moved"]``) and
   stays one step ahead of the host, so the state never visits the host.
   Its prefill takes the head at
   the sampled row only.  What hands a request over as pages of K/V and
   nothing else (the prefix cache, a draft model, remote prefill, the
   tail prefill) is refused for such a model at construction.

10. **Rows that are not tokens.**  For every other model a slot's position
   is also its count of cached rows and the place of its next write.  A
   model whose layers keep an exact window of rows and one pooled row for
   every chunk before it (EVA attention: ``models/eva_decoder.py``) says
   what a slot holds instead, and where (``cache_map``:
   ``ops/eva.py::EvaCacheMap``): a row of the page table is ``[summary
   columns | ring columns]``; at position n a step reads ``128 * (n //
   2048) + n % 2048`` rows (the published window and chunk) through
   ``ops/paged_attention.py`` as it is, over a table row composed on the
   device (the summary pages of the windows before, then the ring); writes
   its new row into the RING, which every window overwrites from its first
   column; and, when its token closes a chunk, pools that page into one
   summary row.  ``_grow`` hands out a summary page every 256 positions
   and ring pages through the first window only: a slot at 32,768
   positions owns 256 pages, not 2,048.  All of it is arithmetic on n, so
   ``lengths`` stays the position, on the device, a step ahead of the
   host.  A prefill of such a model hands over what the cache keeps (the
   open window's rows, every whole chunk's summary) and not its every row.
   What hands a request over as pages of K/V is refused, as in idea 9.

Request/response payloads ride the object plane zero-copy: see
``generate_many`` (client: ``put_many`` prompts → replica:
``get_many`` → decode → ``put_many`` outputs → client: ``get_many``).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import logging
import math
import queue
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ray_tpu import observability as obs
from ray_tpu._private import jax_env
from ray_tpu.exceptions import EngineClosedError, KVPoolExhaustedError
from ray_tpu.serve.sampling import GREEDY, SamplingParams

_DEF = object()  # sentinel: constructor arg not given, consult CONFIG
logger = logging.getLogger(__name__)


def _named(name: str, fn):
    fn.__name__ = fn.__qualname__ = name
    return fn


def _engine_init_span(init):
    """``LLMEngine.__init__`` inside the ``engine.init`` lifecycle span:
    pools and per-slot state allocated, the ``jit`` objects made."""
    @functools.wraps(init)
    def traced(self, *args, **kw):
        jax_env.ensure_compile_listener()
        with obs.span("engine.init", _lifecycle=True) as sp:
            init(self, *args, **kw)
            pools = [self._k_pages, self._v_pages]
            if self._spec:
                pools += [self._dk_pages, self._dv_pages]
            sp.set(slots=self.max_slots,
                   pool_bytes=sum(a.nbytes for a in pools),
                   state_pool_bytes=self._state_pool_bytes())

    return traced


def _attend_uncached(q, k, v):
    """The models' per-layer cache hook when nothing is cached (a full
    prefill): causal self-attention, ``cached_attention``'s S == 0 case."""
    import jax.numpy as jnp

    from ray_tpu.ops.attention import cached_attention

    empty = jnp.zeros((k.shape[0], 0) + k.shape[2:], k.dtype)
    return cached_attention(q, k, v, empty, empty,
                            jnp.zeros((k.shape[0],), jnp.int32))


def _paged_attend(n_layers, k_pages, v_pages, table, lengths, active,
                  first_page=None, attend=None):
    """The models' per-layer cache hooks of the decode programs: attention
    that reads each slot's live pages from the pool in place
    (``ops/paged_attention.py``).  A free lane reads nothing.  ``attend``:
    the model's own reader of the pool in the kernel's place, with its
    arguments (``_sparse_attend``: a model whose layers choose the rows
    they attend to hands the hook what the choice needs and gets the chosen
    rows alone, ``ops/dsa.py``; ``_latent_attend``: a model whose cache is
    one pool of latent rows gets the kernel's latent form)."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import paged_attention

    cached = jnp.where(active, lengths, 0)
    return [functools.partial(
        attend or paged_attention, k_pool=k_pages, v_pool=v_pages, layer=i,
        table=table, lengths=cached, first_page=first_page)
        for i in range(n_layers)]


def _sparse_attend(model):
    """The model's own cache hook (``sparse_paged_attend``), where its
    layers select the cached rows they attend to (``models/glm_dsa.py``);
    None for every other model, whose programs stay what they were."""
    return getattr(model, "sparse_paged_attend", None)


def _latent_attend(model):
    """The cache hook of a model whose cache is ONE pool of latent rows
    (``latent_paged_attend``: ``models/latent_moe.py``), the latent form of
    the paged kernel; None for every other model, whose pools and programs
    stay what they were."""
    return getattr(model, "latent_paged_attend", None)


def _cache_map(model, page_size: int, max_ctx: int):
    """What a slot holds, and where, for a model whose cached rows are not
    its tokens (``cache_map``: ``models/eva_decoder.py``); None for every
    other model, whose programs and bookkeeping stay what they were."""
    declared = getattr(model, "cache_map", None)
    return None if declared is None else declared(page_size, max_ctx)


def _write_mapped(cmap, model, params, k_pages, v_pages, table, lengths,
                  active, new_kvs):
    """The scatter of a decode step whose cache follows a map
    (``ops/eva.py::EvaCacheMap``): every live slot's new row into its
    ring, and, for the slots whose token closes a chunk, that chunk's
    summary row, which the model makes of the ring page and the new row
    (``model.close_chunks``).  A free lane's rows, and the summary of a slot
    that closes nothing, go to the scratch page."""
    import jax
    import jax.numpy as jnp

    slots = jnp.arange(table.shape[0])
    ring = jnp.where(active, table[slots, cmap.ring_column(lengths)], 0)
    closes = active & cmap.closes_chunk(lengths)
    summary = jnp.where(closes, table[slots, cmap.summary_column(lengths)], 0)
    page_idx = jnp.concatenate([ring, summary])
    off = jnp.concatenate([cmap.offset(lengths),
                           cmap.summary_offset(lengths)])

    def as_stored(rows):  # a layer's [slots, 1, H, D] -> [L, slots, H * D]
        rows = jnp.stack(rows)
        return rows.reshape(rows.shape[:2] + (-1,)).astype(k_pages.dtype)

    new = [as_stored([nk[i] for nk in new_kvs]) for i in (0, 1)]
    with jax.named_scope("close_chunks"):
        pooled = model.close_chunks(params, k_pages, v_pages,
                                    jnp.where(closes, ring, 0), *new)
    return tuple(_write_rows(pages, jnp.concatenate([rows, both], axis=1),
                             page_idx, off)
                 for pages, rows, both in zip((k_pages, v_pages), new,
                                              pooled))


def _write_kept(cmap, k_pages, v_pages, row, p, kept):
    """The scatter of a prefill whose model hands over what the cache keeps
    (a layer: ring k, ring v, summary k, summary v, a batch of one): the
    open window's ``p % window`` rows into the ring and the summaries of the
    ``p // chunk`` whole chunks into the summary pages; whatever else the
    bucket computed (its padding, the chunks that hold some) goes to the
    scratch page."""
    import jax.numpy as jnp

    r = jnp.arange(kept[0][0].shape[1])       # offsets into the open window
    at = jnp.arange(kept[0][2].shape[1]) * cmap.chunk  # a chunk's first row
    page_idx = jnp.concatenate([
        jnp.where(r < cmap.window_rows(p), row[cmap.ring_column(r)], 0),
        jnp.where(at < p // cmap.chunk * cmap.chunk,
                  row[cmap.summary_column(at)], 0)])
    off = jnp.concatenate([cmap.offset(r), cmap.summary_offset(at)])
    return tuple(
        _write_rows(pages, jnp.stack([
            jnp.concatenate([layer[i][0], layer[i + 2][0]])
            for layer in kept]), page_idx, off)
        for i, pages in enumerate((k_pages, v_pages)))


def _write_new(pages, rows, page_idx, off):
    """``_write_rows`` of the layers' new rows (a list, one a layer).  A
    model whose cache is one row a token hands no V rows (None), and its V
    pool, which has no page, is left as it is."""
    import jax.numpy as jnp

    if rows[0] is None:
        return pages
    return _write_rows(pages, jnp.stack(rows), page_idx, off)


def _rows_read(sown):
    """From what a decode step's selecting layers sowed
    (``kv_rows_read``): int32, the rows the live slots' softmax ran over,
    summed over the layers.  The program's own count, as
    ``experts_streamed`` is the expert layer's."""
    import jax.numpy as jnp
    from flax import traverse_util

    return sum(value for path, sowed in
               traverse_util.flatten_dict(sown).items()
               if path[-1] == "kv_rows_read"
               for value in sowed).astype(jnp.int32)


def _rows_selected(sown):
    """From what a decode step's selecting layers sowed into ``dsa`` for a
    recording engine (``selected``): int32 [layers, slots, k], the
    positions each slot's softmax ran over in the layers' order, -1 past
    the rows there are."""
    import jax.numpy as jnp
    from flax import traverse_util

    found = {path: sowed[0] for path, sowed in
             traverse_util.flatten_dict(sown).items()
             if path[-1] == "selected"}
    in_order = sorted(found, key=lambda path: [
        int(n) for n in re.findall(r"\d+", "/".join(path))])
    return jnp.stack([found[path] for path in in_order]).astype(jnp.int32)


def _write_rows(pages, rows, page_idx, off):
    """Row ``n`` of every layer of ``rows`` (``[L, n..., Hkv, D]`` or
    ``[L, n..., Hkv*D]``) goes to ``pages[:, page_idx[n], off[n]]``; the
    updated pool is the donated one, written in place.  The pool is
    addressed as the ``[L*P*ps, width]`` rows it is made of: scattering
    into ``[L, P, ps, ...]`` with the layers as a window makes the
    compiler re-lay the whole pool out, there and back, around the
    scatter (9.7 ms a step on the v5e for GPT-2 medium's 2 x 806 MB)."""
    import jax.numpy as jnp

    n_layers, n_pages, ps, width = pages.shape
    row = (jnp.arange(n_layers)[:, None] * n_pages
           + page_idx.reshape(1, -1)) * ps + off.reshape(1, -1)
    rows = rows.reshape(row.size, -1).astype(pages.dtype)
    if rows.shape[1] < width:  # a toy model's row, see pool_width
        rows = jnp.pad(rows, ((0, 0), (0, width - rows.shape[1])))
    flat = pages.reshape(-1, width).at[row.reshape(-1)].set(rows)
    return flat.reshape(pages.shape)


def _routes(model) -> bool:
    """Whether the model has routed expert layers (they then sow each
    token's chosen experts, ``models/llama.py::LlamaMoE``)."""
    return bool(getattr(model.config, "num_experts", 0))


def _kv_layers(model) -> int:
    """Layers that write K/V rows, one layer of the page pool each: what
    the model says (``kv_layers``: a model whose layers differ in kind,
    ``models/nemotron_h.py``), else every layer."""
    return getattr(model, "kv_layers", model.config.num_layers)


def _experts_touched(sown, active, num_experts, held=None):
    """From what a decode step's expert layers sowed (a layer: the chosen
    experts ``expert_idx`` [slots, 1, k], and ``experts_streamed``, how
    many experts' weights it read): int32 [3], summed over layers, of the
    experts that got at least one row of an active slot, of the busiest
    expert's rows, and of the experts streamed.  A free lane's garbage row
    counts for nothing.  ``held`` (first, count): the experts this program
    holds of each layer's ``num_experts``; only they count as hit, and a
    fourth number sums what the layers sowed as ``local_choices``, the
    active rows' choices that landed on them.  Where every expert is held
    that number is the rows' choices, which the host knows: it is left out
    there, and the program of such a model stays the one it was."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    hit = busiest = streamed = local = 0
    for path, sowed in traverse_util.flatten_dict(sown).items():
        for value in sowed:
            if path[-1] == "experts_streamed":
                streamed += value
            elif path[-1] == "local_choices":
                local += value
            elif path[-1] == "expert_idx":
                rows = jnp.sum(jax.nn.one_hot(value[:, 0], num_experts,
                                              dtype=jnp.int32)
                               * active[:, None, None], axis=(0, 1))
                if held is not None:
                    rows = rows[held[0]:held[0] + held[1]]
                hit += jnp.sum(rows > 0)
                busiest += jnp.max(rows)
    counts = [hit, busiest, streamed] + ([] if held is None else [local])
    return jnp.stack(counts).astype(jnp.int32)


def _experts_chosen(sown):
    """From what a program's expert layers sowed: int32 [expert layers,
    rows, k], every row's chosen experts in the layers' order (a decode
    step's rows are its slots, a prefill's its bucket)."""
    import jax.numpy as jnp
    from flax import traverse_util

    found = {path: sowed[0] for path, sowed in
             traverse_util.flatten_dict(sown).items()
             if path[-1] == "expert_idx"}
    in_order = sorted(found, key=lambda path: [
        int(n) for n in re.findall(r"\d+", "/".join(path))])
    return jnp.stack([found[path].reshape(-1, found[path].shape[-1])
                      for path in in_order]).astype(jnp.int32)


def _cfg(name, given, fallback):
    if given is not _DEF and given is not None:
        return given
    try:
        from ray_tpu._private.config import CONFIG

        v = CONFIG.get(name)
        return v if v else fallback
    except Exception:
        return fallback


class PagePool:
    """Free-list allocator of fixed-size KV-cache pages.

    The SegmentPool design (`_private/object_store.py:163`) applied to
    device memory: pages are created once (the device arrays are
    allocated up front) and recycled through a free list instead of
    re-allocated, so steady-state admission costs a list pop.  Pages are
    uniform, so SegmentPool's power-of-two size classes collapse to one
    free list; the accounting (hits/misses, peak, in-use) keeps the same
    shape so the dashboard reads both pools alike.  Page 0 is the
    scratch page: masked-out lanes of the compiled scatter (inactive
    slots, prompt padding) are routed there so they can never corrupt a
    live sequence."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("PagePool needs >= 2 pages (page 0 is scratch)")
        self.capacity = num_pages - 1  # page 0 reserved
        self._free: collections.deque = collections.deque(range(1, num_pages))
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.peak_in_use = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop n pages, all-or-nothing (a partial grant would deadlock the
        grower against its own reservation)."""
        with self._lock:
            if len(self._free) < n:
                self.misses += 1
                return None
            self.hits += 1
            out = [self._free.popleft() for _ in range(n)]
            self.peak_in_use = max(self.peak_in_use, self.in_use)
            return out

    def free(self, pages: Sequence[int]):
        with self._lock:
            self._free.extend(pages)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"capacity": self.capacity, "free": len(self._free),
                    "in_use": self.in_use, "peak_in_use": self.peak_in_use,
                    "hits": self.hits, "misses": self.misses}


@dataclasses.dataclass
class _Request:
    id: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int]
    sampling: SamplingParams = GREEDY
    # Span clock.  Where the wait in the queue starts: at submit, and
    # anew when a preemption puts the request back.
    submitted: float = dataclasses.field(default_factory=time.perf_counter)
    pending_ahead: int = 0   # requests queued before it at submit
    queue_wait: float = 0.0  # seconds, set as admission takes it off
    first_token: float = 0.0  # span clock, at its first admission
    preemptions: int = 0
    out: List[int] = dataclasses.field(default_factory=list)
    chunks: "queue.Queue" = dataclasses.field(default_factory=queue.Queue)
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    error: Optional[BaseException] = None
    streamed: int = 0  # tokens already pushed to the chunk stream
    admit_seq: int = -1  # preemption picks the youngest (highest seq)
    # Consumption mark: True once the caller has the terminal state
    # (result() returned / raised, or the None chunk was delivered).
    # The registry's size bound only evicts consumed requests — evicting
    # a finished-but-undrained streaming request would silently lose its
    # tail chunks.
    consumed: bool = False
    spec_proposed: int = 0
    spec_accepted: int = 0
    # Parallel to ``out``: the behavior logprob of each emitted token
    # (raw log-softmax at the chosen token) and the weight version it
    # was sampled under (swap_weights bumps the engine version).
    out_logps: List[float] = dataclasses.field(default_factory=list)
    out_versions: List[int] = dataclasses.field(default_factory=list)
    # ``record_experts``: one [expert layers, k] array for every row fed
    # so far (the context but for the newest token), what its routers
    # chose in the program that computed it.
    record_experts: bool = False
    fed_experts: List[Any] = dataclasses.field(default_factory=list)
    # beside them, where the model's layers select their rows: one
    # [layers, k] array of positions for every row a DECODE step fed since
    # the request's last prefill
    fed_selected: List[Any] = dataclasses.field(default_factory=list)
    # Distributed trace the request was submitted under (the caller's
    # (trace_id, span_id) pair): its request.queued and request.decode
    # spans stamp it, so they land in the client's timeline.
    trace_ctx: Optional[tuple] = None

    def context(self) -> List[int]:
        """Prompt plus generated-so-far — what a (re)admission prefills.
        Decode is seed-deterministic, so a preempted request resumed
        from this context produces exactly the tokens it would have."""
        return self.prompt + self.out

    def finish(self, error: Optional[BaseException] = None):
        self.error = error
        if self.streamed < len(self.out):
            self.chunks.put(self.out[self.streamed:])
            self.streamed = len(self.out)
        self.chunks.put(None)
        self.done.set()


@dataclasses.dataclass
class _Step:
    """A dispatched decode step the host has not read yet: its outputs,
    still on the device with their copies to the host under way, and
    the (slot, request) rows it computed a token for."""
    tokens: Any
    logps: Any
    touched: Any  # a routed model's _experts_touched, else None
    rows: List[tuple]
    chosen: Any = None  # _experts_chosen, where the engine records them
    rows_read: Any = None  # _rows_read, where the model's layers select
    selected: Any = None  # _rows_selected, where it records and they select


class LLMEngine:
    """Replica-resident continuous-batching decode engine.

    ``submit()`` is thread-safe and returns immediately; a background
    flow.Stage (sink mode) owns all device state and serializes
    prefill/decode.  ``result()`` blocks for the full output,
    ``stream()`` yields token chunks as they are produced (chunks
    arrive while the request is still decoding).  Default sampling is
    greedy (argmax) — the token-identity contract with the uncached
    reference is what the correctness gates assert; per-request
    temperature/top-p/seed turn on real (still deterministic)
    sampling."""

    # Registry size bound: evict CONSUMED finished requests past LIMIT,
    # down to FLOOR (a long-lived replica must not leak one _Request per
    # call, but an undrained streaming request is never dropped).
    REGISTRY_LIMIT = 4096
    REGISTRY_FLOOR = 2048

    @_engine_init_span
    def __init__(self, model, params, *, max_slots=_DEF, page_size=_DEF,
                 num_pages: Optional[int] = None,
                 max_ctx: Optional[int] = None,
                 chunk_tokens: int = 8, start: bool = True,
                 draft_model=None, draft_params=None, spec_tokens=_DEF,
                 draft_window: Optional[int] = None,
                 prefix_cache=None, cache_namespace: str = "",
                 prefix_directory=None, directory_timeout_s: float = 5.0,
                 prefill=None, prefill_min_tokens=_DEF,
                 record_experts: bool = False):
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self._model = model
        self._params = params
        c = model.config
        self.num_layers = _kv_layers(model)  # of the page pool
        self.head_dim = c.head_dim
        self.kv_heads = getattr(c, "num_kv_heads", c.num_heads)
        self.dtype = c.dtype
        self.max_slots = int(_cfg("serve_max_slots", max_slots, 8))
        self.page_size = int(_cfg("serve_page_size", page_size, 16))
        self.max_ctx = int(max_ctx or c.max_position_embeddings)
        self.pages_per_slot = math.ceil(self.max_ctx / self.page_size)
        self.max_ctx = self.pages_per_slot * self.page_size
        if self.max_ctx > c.max_position_embeddings:
            raise ValueError(
                f"max_ctx {self.max_ctx} (page-rounded) exceeds the model's "
                f"max_position_embeddings {c.max_position_embeddings}")
        # A model whose cached rows are not its tokens says how many pages
        # a slot owns at ``max_ctx`` (idea 10 of the module's docstring).
        self._cmap = _cache_map(model, self.page_size, self.max_ctx)
        if self._cmap is not None:
            self.pages_per_slot = self._cmap.pages_per_slot
        # Default pool: full provisioning (+1 scratch) — every slot can
        # reach max_ctx, preemption never fires.  Size it down to share
        # the pool across more slots than worst-case memory allows.
        if num_pages is None:
            num_pages = self.max_slots * self.pages_per_slot + 1
        self.pool = PagePool(num_pages)
        self.chunk_tokens = chunk_tokens

        # A page is one lane-dense [page_size, kv_heads * head_dim] tile:
        # what the paged-attention kernel copies whole, and no head of 64
        # padded out to a 128-lane register.
        from ray_tpu.ops.paged_attention import pool_width

        shape = (self.num_layers, num_pages, self.page_size,
                 pool_width(self.kv_heads, self.head_dim))
        self._k_pages = jnp.zeros(shape, self.dtype)
        # A model whose cache is one latent row a token (``[c | rope(k_r)]``,
        # the values its first columns: ``models/latent_moe.py``) gets ONE
        # pool: its V pool has no page, and every program hands it through.
        self._latent = _latent_attend(model) is not None
        self._v_pages = jnp.zeros(
            (shape[0], 0) + shape[2:] if self._latent else shape, self.dtype)
        # Where the page pool lives is where the engine decodes.
        self._device = next(iter(self._k_pages.devices()))

        # ---- per-slot recurrent state ----
        # What the model says a slot holds besides pages of K/V
        # (``slot_state``: name -> (shape, dtype), one set a layer, or a
        # layer of those the model counts as ``state_layers``; GPT-2 and
        # Llama say nothing): one array a layer a kind, so that each is
        # updated in place (idea 9 of the module's docstring).
        self._state = None
        spec = getattr(model, "slot_state", None)
        # the options that hand a request over as pages of K/V, where given
        handed_over = [name for name, given in (
            ("prefix_cache", prefix_cache),
            ("prefix_directory", prefix_directory),
            ("draft_model", draft_model), ("prefill", prefill))
            if given is not None and given is not False]
        if spec:
            if handed_over:
                raise ValueError(
                    f"{handed_over[0]}= cannot serve a model with per-slot "
                    "recurrent state: a cached prefix, a draft's "
                    "window and a remote prefill all hand over pages "
                    "of K/V and no state snapshot")
            self._state = [
                {k: jnp.zeros((self.max_slots,) + tuple(shape), dtype)
                 for k, (shape, dtype) in spec.items()}
                for _ in range(getattr(model, "state_layers",
                                       c.num_layers))]

        # ---- layers that select the rows they attend to ----
        # (``models/glm_dsa.py``): a decode step reads the pool through the
        # model's own hook.  What hands a request over without this
        # engine's full prefill is refused: a tail prefill attends to the
        # cached prefix densely, a remote prefill and a draft's verify
        # window know nothing of the selection.
        if self._latent and handed_over:
            raise ValueError(
                f"{handed_over[0]}= cannot serve a model whose cache is one "
                "pool of latent rows: a cached prefix, a draft's window and "
                "a remote prefill all hand over pages of K AND V")
        if self._cmap is not None and handed_over:
            raise ValueError(
                f"{handed_over[0]}= cannot serve a model whose cached rows "
                "are not its tokens: a cached prefix, a draft's window and a "
                "remote prefill all hand over one page of K/V for every "
                "page of positions, not a ring and summaries")
        self._sparse = _sparse_attend(model) is not None
        # a prefill that is told its bucket's real rows and takes the head
        # at the last of them alone
        self._ragged = self._sparse or getattr(model, "prefill_lengths",
                                               False)
        if self._sparse and handed_over:
            raise ValueError(
                f"{handed_over[0]}= cannot serve a model with learned "
                "sparse attention: the tail prefill, the verify window and "
                "a remote prefill attend to every cached row, not to the "
                "rows the indexer selects")

        # ---- the routers' choices, for whoever asks with a request ----
        # The decode program and the full prefills then also return what
        # each row's routers chose, and a request submitted with
        # ``record_experts=True`` gets them with its rollout (a learner
        # that replays the routing it sampled under; a reference that is
        # given the program's choices).  Off, every program is the one it
        # was.
        self.record_experts = bool(record_experts)
        if self.record_experts:
            if not _routes(model):
                raise ValueError("record_experts= needs a model with "
                                 "routed expert layers")
            if handed_over:
                raise ValueError(
                    f"{handed_over[0]}= hands over rows whose routers' "
                    "choices this engine did not see: not with "
                    "record_experts=")

        # ---- speculative decoding (draft + verify) ----
        self.spec_tokens = int(_cfg("serve_spec_tokens", spec_tokens,
                                    4 if draft_model is not None else 0))
        self._draft_model = draft_model
        self._draft_params = draft_params
        self._spec = draft_model is not None and self.spec_tokens >= 2
        if draft_model is not None and not self._spec:
            raise ValueError(
                f"speculative decoding needs spec_tokens >= 2, got "
                f"{self.spec_tokens}")
        if self._spec:
            dc = draft_model.config
            if dc.vocab_size != c.vocab_size or \
                    dc.max_position_embeddings < self.max_ctx:
                raise ValueError(
                    "draft model must share the target's vocab and cover "
                    "its max_ctx "
                    f"(draft vocab {dc.vocab_size} vs {c.vocab_size}, "
                    f"positions {dc.max_position_embeddings} vs "
                    f"{self.max_ctx})")
            dshape = (_kv_layers(draft_model), num_pages, self.page_size,
                      pool_width(getattr(dc, "num_kv_heads", dc.num_heads),
                                 dc.head_dim))
            self._dk_pages = jnp.zeros(dshape, dc.dtype)
            self._dv_pages = jnp.zeros(dshape, dc.dtype)
        # Sliding-window draft attention: the draft reads only the newest
        # ceil(draft_window / page_size) pages of a slot.
        self._draft_window_pages = None
        if draft_window is not None:
            if not self._spec:
                raise ValueError("draft_window needs a draft model")
            self._draft_window_pages = max(
                2, math.ceil(int(draft_window) / self.page_size))

        # ---- prefix cache ----
        from ray_tpu.serve import prefix_cache as pc

        if prefix_cache is True:
            prefix_cache = pc.PrefixCacheLocal(
                int(_cfg("serve_prefix_cache_bytes", _DEF,
                         256 * 1024 * 1024)))
        self._prefix = prefix_cache or None
        self._directory = prefix_directory
        self._directory_timeout = float(directory_timeout_s)
        if not cache_namespace:
            cache_namespace = (f"{type(model).__name__}|{c!r}|"
                               f"ps{self.page_size}")
        # The engine owns version-folding: callers pass the UNVERSIONED
        # base namespace and every swap_weights re-derives the effective
        # namespace, making pre-swap pages unaddressable (see
        # prefix_cache.versioned_namespace).
        self._base_namespace = cache_namespace
        self._weight_version = 0
        self._namespace = pc.versioned_namespace(cache_namespace, 0)
        # Refs for pages this replica published: keeps the object alive
        # across the publish handoff even if the directory is slow to
        # pin; bounded (the directory is the durable holder).
        self._published_refs: "collections.OrderedDict[str, tuple]" = \
            collections.OrderedDict()

        # ---- disaggregated prefill ----
        self._prefill_min = int(_cfg("serve_prefill_min_tokens",
                                     prefill_min_tokens, 32))
        self._prefill_client = None
        if prefill is not None:
            from ray_tpu.serve.prefill import as_prefill_client

            self._prefill_client = as_prefill_client(prefill)
        # (req, job, start_tokens) awaiting remote KV — NOTHING is
        # reserved while a prefill is in flight (a held slot would
        # starve interactive admissions behind a long-prompt burst);
        # completed payloads park in _ready until a slot frees.
        self._awaiting: List[tuple] = []
        self._ready: collections.deque = collections.deque()
        self._prefill_max_inflight = 2 * self.max_slots

        # Host-side slot state (the loop thread is the only writer).
        self._table = np.zeros((self.max_slots, self.pages_per_slot),
                               np.int32)
        self._lengths = np.zeros((self.max_slots,), np.int32)
        self._active = np.zeros((self.max_slots,), bool)
        self._last_tok = np.zeros((self.max_slots,), np.int32)
        self._temps = np.zeros((self.max_slots,), np.float32)
        self._top_ps = np.ones((self.max_slots,), np.float32)
        self._seeds = np.zeros((self.max_slots,), np.int32)
        # The plain decode loop runs one step ahead of the host
        # (_decode_once).  _budget: the decode tokens a slot may still
        # have dispatched (max_new_tokens less those emitted and in
        # flight), so a slot is out of the first step it does not need;
        # _fresh: slots admitted since the last dispatch, whose input
        # token is the prefill's, on the host, where a continuing slot's
        # is the last step's, on the device (_prev_tok); _resident: the
        # device's copy of each array of slot state with the host value
        # it holds, sent again only when the host's differs.
        self._budget = np.zeros((self.max_slots,), np.int32)
        self._fresh = np.zeros((self.max_slots,), bool)
        self._prev_tok = jnp.zeros((self.max_slots,), jnp.int32)
        self._resident: Dict[str, tuple] = {}
        self._inflight: Optional[_Step] = None
        self._slot_pages: List[List[int]] = [[] for _ in range(self.max_slots)]
        self._slot_req: Dict[int, _Request] = {}

        self._decode = self._program(
            "llm_decode", self._make_decode_step(
                model, record_experts=self.record_experts),
            # pools, and the state (``step``'s thirteenth argument)
            donate_argnums=(1, 2) if self._state is None else (1, 2, 12))
        if self._spec:
            self._draft_decode = self._program(
                "llm_draft_decode", self._make_decode_step(
                    draft_model, window_pages=self._draft_window_pages),
                donate_argnums=(1, 2))
            self._verify = self._program(
                "llm_verify", self._make_verify_step(model),
                donate_argnums=(1, 2))
        self._adopt = self._program(
            "llm_adopt", self._make_adopt(self.dtype),
            donate_argnums=(0, 1))
        self._adopt_buf_k = np.zeros(
            (self.num_layers, self.pages_per_slot) + self._k_pages.shape[2:],
            np.float32)
        self._adopt_buf_v = np.zeros_like(self._adopt_buf_k)
        self._prefills: Dict[Any, Any] = {}

        self._pending: collections.deque = collections.deque()
        self._requests: Dict[int, _Request] = {}
        self._next_id = 0
        self._admit_counter = 0
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._closed = False
        self._stats = collections.Counter()
        self._occupancy_sum = 0.0
        # Routed models: choices a row makes in one step (expert layers x
        # top-k) and experts a step can touch (those this program holds,
        # where the model says it holds a share: ``experts_held``), for the
        # moe_* shares of stats().
        routed = getattr(model, "expert_layers", c.num_layers)
        self._moe_choices = routed * getattr(c, "num_experts_per_tok", 0)
        self._moe_experts = routed * getattr(
            c, "experts_held", getattr(c, "num_experts", 0))
        self._moe_busiest_share_sum = 0.0
        self._t0 = time.monotonic()
        # Hot weight swap: queued (params_or_ref, version, event) applied
        # by the loop thread at the next token boundary.
        self._pending_swaps: collections.deque = collections.deque()
        self._swap_latency_sum = 0.0
        # Generation-plane accounting for the RLHF overlap gates: wall
        # time spent doing device work (prefill/decode/swap) and the
        # completion stamp of recent decode steps.
        self._work_s = 0.0
        self._step_stamps: collections.deque = collections.deque(
            maxlen=1024)
        # Handle calls this process answered (note_reply_call): the load
        # the loop thread shares its interpreter with.  next() hands each
        # call its number without a lock; the store after it may land
        # behind another thread's, so a reader can see the count a call
        # or two short for the length of a call, and nothing is lost.
        self._reply_seq = itertools.count(1)
        self._reply_calls = 0
        self._reply_calls_seen = 0  # at the last iteration's close
        # slot-state arrays sent to the device again, and their bytes
        self._uploads = self._upload_bytes = 0
        self._metrics = None
        self._metrics_flush = 0.0
        self._stage = None
        if start:
            # The engine loop is a sink stage on the async dataflow
            # substrate: the tick source runs until the stage's token
            # cancels, one fn call per engine iteration, and close()
            # joins the worker thread through the substrate.
            from ray_tpu.parallel import flow

            self._stage = flow.Stage(
                self._tick_source(), self._iteration, sink=True, workers=1,
                name="llm_engine", span="", export_metrics=False)

    # ------------------------------------------------------------------
    # public API (any thread)
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               temperature: Optional[float] = None,
               top_p: Optional[float] = None,
               seed: Optional[int] = None,
               record_experts: bool = False) -> int:
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if record_experts and not self.record_experts:
            raise ValueError("record_experts=True needs an engine built "
                             "with record_experts=True: its programs "
                             "return the routers' choices")
        if len(prompt) + max_new_tokens > self.max_ctx:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_ctx {self.max_ctx}")
        if sampling is None:
            sampling = SamplingParams(
                temperature=0.0 if temperature is None else float(temperature),
                top_p=1.0 if top_p is None else float(top_p),
                seed=0 if seed is None else int(seed))
        sampling.validate()
        trace_ctx = obs.get_context() if obs.enabled() else None
        with self._cond:
            if self._closed:
                raise EngineClosedError("engine is closed")
            rid = self._next_id
            self._next_id += 1
            req = _Request(rid, prompt, max_new_tokens, eos_id,
                           sampling=sampling, trace_ctx=trace_ctx,
                           pending_ahead=len(self._pending),
                           record_experts=record_experts)
            self._requests[rid] = req
            self._pending.append(req)
            self._cond.notify_all()
        return rid

    def result(self, rid: int, timeout: Optional[float] = None) -> List[int]:
        req = self._requests[rid]
        if not req.done.wait(timeout):
            raise TimeoutError(f"request {rid} not done within {timeout}s")
        req.consumed = True
        if req.error is not None:
            raise req.error
        return list(req.out)

    def swap_weights(self, params, version: int,
                     timeout: Optional[float] = 60.0) -> int:
        """Install new model params at the next token boundary (hot swap).

        ``params`` is either a host/device param pytree or an
        ``ObjectRef`` from the versioned one-put weight broadcast (the
        learner ``put``s once; every replica resolves the same ref) —
        either way the engine pays exactly ONE ``device_put`` per
        version.  The compiled decode/prefill/verify programs take
        params as a plain argument, so a swap never recompiles
        (``decode_cache_size`` stays 1) and no in-flight request is
        dropped: active slots are recycled through the
        recompute-preemption path, which re-prefills their
        prompt+generated-so-far context under the NEW weights — their
        already-emitted tokens (and captured logprobs/version stamps)
        are data and survive verbatim, and every later token is sampled
        under, and stamped with, ``version``.  The prefix-cache
        namespace re-derives with the new version, so pre-swap KV pages
        can never be adopted into post-swap contexts.

        ``version`` must be strictly greater than the current engine
        version (stamps must be unambiguous).  With ``timeout`` the call
        blocks until the loop applies the swap (raises ``TimeoutError``
        otherwise); ``timeout=None`` returns immediately.  Returns the
        installed version."""
        version = int(version)
        applied = threading.Event()
        with self._cond:
            if self._closed:
                raise EngineClosedError("engine is closed")
            pending_max = max(
                [v for _, v, _ in self._pending_swaps],
                default=self._weight_version)
            if version <= pending_max:
                raise ValueError(
                    f"swap version {version} must exceed the current "
                    f"version {pending_max}")
            self._pending_swaps.append((params, version, applied))
            self._cond.notify_all()
        if timeout is not None:
            if not applied.wait(timeout):
                raise TimeoutError(
                    f"weight swap to version {version} not applied within "
                    f"{timeout}s")
            if self._weight_version < version:
                # close()/_fail_all wakes waiters without applying.
                raise EngineClosedError(
                    f"engine closed before swap to version {version} "
                    f"applied")
        return version

    @property
    def weight_version(self) -> int:
        return self._weight_version

    def rollout(self, rid: int, timeout: Optional[float] = None
                ) -> Dict[str, Any]:
        """Blocking full result PLUS the per-token behavior logprobs and
        weight-version stamps — the RLHF rollout record (no second
        forward pass needed for the PPO ratio).  For a request submitted
        with ``record_experts=True`` also ``experts``: int32 [rows fed,
        expert layers, k], what the routers chose on each row in the
        program that computed it, and, where the model's layers select the
        rows they attend to, ``selected``: int32 [rows a decode step fed,
        layers, index_topk], the positions each of those rows' softmax ran
        over (-1 past the rows there were)."""
        req = self._requests[rid]
        if not req.done.wait(timeout):
            raise TimeoutError(f"request {rid} not done within {timeout}s")
        req.consumed = True
        if req.error is not None:
            raise req.error
        out = {
            "prompt": list(req.prompt),
            "tokens": list(req.out),
            "logprobs": list(req.out_logps),
            "versions": list(req.out_versions),
        }
        if req.record_experts:
            # [rows fed, expert layers, k]: the prompt's rows and every
            # answered token's but the last, which no program was fed
            out["experts"] = np.stack(req.fed_experts)
            if self._sparse:
                out["selected"] = np.stack(req.fed_selected) \
                    if req.fed_selected else np.zeros((0, 0, 0), np.int32)
        return out

    def generate_rollouts(self, prompts: Sequence[Sequence[int]],
                          max_new_tokens: int = 16,
                          eos_id: Optional[int] = None,
                          sampling: Optional[List[SamplingParams]] = None,
                          timeout: float = 300.0) -> List[Dict[str, Any]]:
        """Submit a prompt batch and collect version-stamped rollouts —
        continuous batching amortizes the decode across the whole batch
        (all prompts are in flight together, subject to ``max_slots``)."""
        if sampling is None:
            sampling = [None] * len(prompts)
        rids = [self.submit(p, max_new_tokens, eos_id, sampling=s)
                for p, s in zip(prompts, sampling)]
        return [self.rollout(r, timeout=timeout) for r in rids]

    def recent_step_stamps(self) -> List[float]:
        """``time.monotonic()`` completion stamps of recent decode steps
        — the overlap gates prove generation ran inside an SGD window by
        finding stamps inside it."""
        with self._lock:
            return list(self._step_stamps)

    def stream(self, rid: int, timeout: float = 120.0):
        """Yield token chunks (lists) as they are produced; returns when
        the request retires.  Raises the request's error, if any."""
        req = self._requests[rid]
        while True:
            chunk = req.chunks.get(timeout=timeout)
            if chunk is None:
                break
            yield chunk
        req.consumed = True
        if req.error is not None:
            raise req.error

    def note_reply_call(self):
        """One handle call that this process answers (``LLMServer``'s
        ``next_chunk``, ``submit_stream``, ``request_stats``)."""
        self._reply_calls = next(self._reply_seq)

    def request_stats(self, rid: int) -> Dict[str, Any]:
        """Per-request accounting (speculative acceptance metrics)."""
        req = self._requests[rid]
        return {
            "tokens": len(req.out),
            "spec_proposed": req.spec_proposed,
            "spec_accepted": req.spec_accepted,
            "spec_acceptance_rate": (req.spec_accepted / req.spec_proposed
                                     if req.spec_proposed else 0.0),
        }

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            n_active = int(self._active.sum())
            s = dict(self._stats)
            n_awaiting = len(self._awaiting) + len(self._ready)
        pool = self.pool.stats()
        steps = s.get("steps", 0)
        out = {
            "active": n_active,
            "pending": len(self._pending),
            "admitted": s.get("admitted", 0),
            "admitted_mid_batch": s.get("admitted_mid_batch", 0),
            "completed": s.get("completed", 0),
            "preemptions": s.get("preemptions", 0),
            "steps": steps,
            # the plain loop's step ahead: dispatched under a running
            # step / with none in flight; rows an eos_id made useless
            "lookahead_steps": s.get("lookahead_steps", 0),
            "drained_steps": s.get("drained_steps", 0),
            "late_eos_rows": s.get("late_eos_rows", 0),
            "tokens_generated": s.get("tokens", 0),
            # handle calls answered (next_chunk, submit_stream,
            # request_stats): over tokens_generated, calls a token
            "reply_calls": self._reply_calls,
            "avg_batch_occupancy": (self._occupancy_sum / steps
                                    if steps else 0.0),
            "pages_in_use": pool["in_use"],
            "pages_free": pool["free"],
            "page_pool": pool,
            # per-slot recurrent state beside the pools (0: the model
            # carries none)
            "state_pool_bytes": self._state_pool_bytes(),
            # what one cached token costs the page pool, K and V rows of
            # every layer that writes them, as stored (a row padded to
            # ``pool_width``; a latent row stored in both pools counts
            # twice, one in the one pool of a ``latent_cache`` model once)
            "kv_bytes_per_token": (self._k_pages.nbytes + self._v_pages.nbytes)
            // (self._k_pages.shape[1] * self.page_size),
            # slots whose state the decode steps read and wrote, summed
            # over the steps dispatched (the spans' ``state_slots``): over
            # steps x max_slots, the share of the pool a step touches
            "state_slots_moved": s.get("state_slots_moved", 0),
            "prefill_buckets": len(self._prefills),
            # sampling / speculative decoding
            "greedy_steps": s.get("greedy_steps", 0),
            "sampled_steps": s.get("sampled_steps", 0),
            "spec_steps": s.get("spec_steps", 0),
            "spec_proposed": s.get("spec_proposed", 0),
            "spec_accepted": s.get("spec_accepted", 0),
            "spec_acceptance_rate": (
                s.get("spec_accepted", 0) / s.get("spec_proposed", 1)
                if s.get("spec_proposed", 0) else 0.0),
            # prefix cache
            "prefix_hit_pages": s.get("prefix_hit_pages", 0),
            "prefix_remote_hit_pages": s.get("prefix_remote_hit_pages", 0),
            "prefix_published_pages": s.get("prefix_published_pages", 0),
            "prefill_tokens": s.get("prefill_tokens", 0),
            "prefill_tokens_saved": s.get("prefill_tokens_saved", 0),
            # disaggregated prefill
            "prefill_offloaded": s.get("prefill_offloaded", 0),
            "prefill_inflight": n_awaiting,
            "prefill_prefix_fallback": s.get("prefill_prefix_fallback", 0),
            "wire_bytes": s.get("wire_bytes", 0),
            "wire_fp32_bytes": s.get("wire_fp32_bytes", 0),
            # hot weight swap / generation-plane accounting
            "weight_version": self._weight_version,
            "swaps": s.get("swaps", 0),
            "swap_reprefills": s.get("swap_reprefills", 0),
            "swap_latency_s_avg": (self._swap_latency_sum / s["swaps"]
                                   if s.get("swaps", 0) else 0.0),
            "work_seconds": self._work_s,
        }
        if self._moe_experts:
            # Of the experts a step could touch, the share it did, and the
            # share whose weights it read (the same, where the expert
            # layer follows the step's own list: ``ops/moe.py``); and the
            # busiest expert's share of a step's assignments (1/E when
            # routing is even): means over the decode steps so far.
            for key in ("moe_experts_hit", "moe_experts_streamed"):
                out[key + "_share"] = (
                    s.get(key, 0) / (steps * self._moe_experts)
                    if steps else 0.0)
            out["moe_max_expert_share"] = (
                self._moe_busiest_share_sum / steps if steps else 0.0)
            # How many experts a step can touch (those held here), the
            # totals behind the shares, and of the live rows' choices the
            # share that landed on a held expert: all of them where every
            # expert is held, the held share of a layer where the model
            # holds a share (``experts_held``) and routing is even.
            out["moe_experts_held"] = self._moe_experts
            for key in ("moe_experts_hit", "moe_experts_streamed",
                        "moe_local_choices", "moe_choices"):
                out[key] = s.get(key, 0)
            out["moe_local_choice_share"] = (
                out["moe_local_choices"] / out["moe_choices"]
                if out["moe_choices"] else 0.0)
        if self._sparse:
            # Learned sparse attention: the cached rows the decode steps'
            # indexers scored (the spans' ``index_rows``: what dense
            # attention would have read), the rows their softmax ran over
            # (``kv_rows_read``, the programs' own count, the token's own
            # row among it), and the second over the first.
            out["dsa_rows_scored"] = s.get("dsa_rows_scored", 0)
            out["dsa_rows_read"] = s.get("dsa_rows_read", 0)
            out["dsa_selected_share"] = (
                out["dsa_rows_read"] / out["dsa_rows_scored"]
                if out["dsa_rows_scored"] else 0.0)
        if self._cmap is not None:
            # A cache whose rows are not its tokens: what a slot owns and
            # holds at ``max_ctx`` positions, a token's bytes as stored
            # there, and over the decode steps so far the positions the
            # live slots held, the rows they read and the second over the
            # first (1.0 would be a cache of one row a token).
            m = self._cmap
            out["kv_pages_per_slot"] = m.pages_per_slot
            out["kv_rows_per_slot"] = m.rows_per_slot
            out["kv_positions_per_slot"] = self.max_ctx
            out["kv_bytes_per_token"] = (out["kv_bytes_per_token"]
                                         * m.rows_per_slot // self.max_ctx)
            for key in ("cache_ctx_tokens", "cache_rows_read",
                        "cache_chunks_closed", "cache_windows_closed"):
                out[key] = s.get(key, 0)
            out["cache_rows_share"] = (
                out["cache_rows_read"] / out["cache_ctx_tokens"]
                if out["cache_ctx_tokens"] else 0.0)
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
        cache_size = getattr(self._decode, "_cache_size", None)
        if callable(cache_size):
            out["decode_cache_size"] = cache_size()
        # what JAX compiled or fetched in this process, and how long it
        # took: set-up's, and any bucket first reached under load
        out.update(jax_env.compile_totals())
        out["platform"] = self._device.platform
        out["device_kind"] = self._device.device_kind
        return out

    def _state_pool_bytes(self) -> int:
        return sum(a.nbytes for layer in self._state or ()
                   for a in layer.values())

    def close(self, timeout: float = 10.0):
        with self._cond:
            if self._closed:
                return
            self._closed = True
            swaps = list(self._pending_swaps)
            self._pending_swaps.clear()
            self._cond.notify_all()
        for _, _, applied in swaps:
            applied.set()  # wake blocked swappers; version stays put
        if self._stage is not None:
            self._stage.close()
        ahead, self._inflight = self._inflight, None
        if ahead is not None:  # nothing runs under a closed engine
            self._jax.block_until_ready(ahead.tokens)
        err = EngineClosedError("engine closed with requests in flight")
        for req in list(self._requests.values()):
            if not req.done.is_set():
                req.finish(error=err)

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _make_decode_step(self, model, window_pages: Optional[int] = None,
                          record_experts: bool = False):
        """One token for every slot (fixed shapes — compiled once).
        Inactive lanes compute garbage routed to the scratch page.
        Shared shape for the target and the draft model (each gets its
        own jit over its own page arrays).  Attention reads each slot's
        live pages from the pool in place (``ops/paged_attention.py``).

        ``window_pages`` (draft only) starts that read at the first of
        the LAST n pages: a sliding window.  Positional information is
        baked into the cached K/V at write time (learned embeddings at
        embed, rope at projection), so a later first page is exact
        windowed attention, no re-indexing.  The target never does this
        (it must attend to everything); the draft is a guesser, and the
        verify step catches what the shortened horizon loses.

        ``prev_tokens`` and ``fresh`` (the plain loop, which runs a step
        ahead of the host): a row takes its input token from
        ``prev_tokens``, the step before's output still on the device,
        unless ``fresh`` marks it as admitted since, with its prefill's
        token in ``tokens``.  One program decides per row.  Without them
        every row takes ``tokens`` (the draft, whose proposals the host
        compares).  The step also returns the lengths it leaves behind,
        so they too stay on the device."""
        jnp = self._jnp
        L, ps, pp = _kv_layers(model), self.page_size, self.pages_per_slot
        from ray_tpu.serve.sampling import sample_tokens_with_logprobs

        scope = self._jax.named_scope
        routes = _routes(model)
        sparse = _sparse_attend(model)
        own_hook = sparse or _latent_attend(model)
        cmap = self._cmap  # the target's: a draft is refused beside a map
        held = getattr(model.config, "experts_held", None)
        if held is not None:  # a share: (first expert, how many)
            held = (model.config.expert_offset, held)

        def step(params, k_pages, v_pages, table, lengths, tokens, active,
                 temps, top_ps, seeds, prev_tokens=None, fresh=None,
                 state=None):
            if fresh is not None:
                tokens = jnp.where(fresh, tokens, prev_tokens)
            # recurrent state: advanced where ``active``; expert layers:
            # only the experts an ``active`` row chose are read
            carried = {} if state is None else {"state": state}
            if routes or state is not None:
                carried["active"] = active
            with scope("attend"):
                first = None
                if window_pages is not None and window_pages < pp:
                    # Pages [(len-1)//ps - wp + 1 .. (len-1)//ps],
                    # clamped: the newest wp pages.
                    last_page = jnp.maximum(lengths - 1, 0) // ps
                    first = jnp.maximum(last_page - (window_pages - 1), 0)
                # where the cached rows are not the tokens: the row of
                # pages to follow and the live rows of it, from the position
                read, cached = (table, lengths) if cmap is None else \
                    cmap.read_table(table, lengths)
                out = model.apply(
                    {"params": params}, tokens[:, None], lengths[:, None],
                    _paged_attend(L, k_pages, v_pages, read, cached,
                                  active, first, own_hook),
                    mutable=(["moe", "dsa"] if record_experts and sparse
                             else ["moe"]) if routes else False, **carried)
                (logits, new_kvs, *state), sown = out if routes else (
                    out, None)
            # The generated token sits at absolute position lengths + 1.
            with scope("sample"):
                next_tok, next_logp = sample_tokens_with_logprobs(
                    logits[:, -1], lengths + 1, temps, top_ps, seeds)
            with scope("scatter"):
                # a layer's rows: [slots, 1, Hkv, D]
                if cmap is not None:
                    k_pages, v_pages = _write_mapped(
                        cmap, model, params, k_pages, v_pages, table,
                        lengths, active, new_kvs)
                else:
                    slot_ix = jnp.arange(table.shape[0])
                    page_col = jnp.minimum(lengths // ps, pp - 1)
                    page_idx = jnp.where(active, table[slot_ix, page_col], 0)
                    off = lengths % ps
                    k_pages = _write_new(k_pages, [nk[0] for nk in new_kvs],
                                         page_idx, off)
                    v_pages = _write_new(v_pages, [nk[1] for nk in new_kvs],
                                         page_idx, off)
            out = (k_pages, v_pages, next_tok, next_logp,
                   lengths + active.astype(lengths.dtype))
            if routes:
                out += (_experts_touched(sown, active,
                                         model.config.num_experts, held),)
            if record_experts:
                out += (_experts_chosen(sown),)
                if sparse is not None:
                    out += (_rows_selected(sown["dsa"]),)
            if sparse is not None:
                out += (_rows_read(sown),)
            return out + tuple(state)  # the state, last, where there is one

        return step

    def _make_verify_step(self, model):
        """Target-model verification of a [slots, k] speculative window:
        one forward over the window (the decode step's attention with k
        query rows a slot), KV scattered for every position, and the
        target's sampled token at every position — the host applies
        accept-longest-prefix to the result."""
        jnp = self._jnp
        L, ps, pp = _kv_layers(model), self.page_size, self.pages_per_slot
        k_win = self.spec_tokens
        from ray_tpu.serve.sampling import sample_tokens_with_logprobs

        def verify(params, k_pages, v_pages, table, lengths, window, active,
                   temps, top_ps, seeds):
            positions = lengths[:, None] + jnp.arange(k_win)[None]
            logits, new_kvs = model.apply(
                {"params": params}, window, positions,
                _paged_attend(L, k_pages, v_pages, table, lengths, active))
            newk = jnp.stack([nk[0] for nk in new_kvs])  # [L,slots,k,Hkv,D]
            newv = jnp.stack([nk[1] for nk in new_kvs])
            page_col = jnp.minimum(positions // ps, pp - 1)
            page_idx = jnp.where(active[:, None],
                                 jnp.take_along_axis(table, page_col, axis=1),
                                 0)
            off = positions % ps
            k_pages = _write_rows(k_pages, newk, page_idx, off)
            v_pages = _write_rows(v_pages, newv, page_idx, off)
            n = table.shape[0]
            flat = logits.reshape(n * k_win, -1)
            rep = lambda a: jnp.repeat(a, k_win)
            sampled, logps = sample_tokens_with_logprobs(
                flat, (positions + 1).reshape(-1), rep(temps), rep(top_ps),
                rep(seeds))
            return (k_pages, v_pages, sampled.reshape(n, k_win),
                    logps.reshape(n, k_win))

        return verify

    def _make_adopt(self, dtype):
        """Scatter host-staged KV pages (prefix-cache hits, disaggregated
        prefill payloads) into the device page arrays.  Fixed
        [pages_per_slot] shape — compiled once; unused rows are routed
        to the scratch page by the host-masked ids."""
        jnp = self._jnp

        def adopt(k_pages, v_pages, page_ids, k_new, v_new):
            _, n, ps, _ = k_new.shape  # [L, pages, ps, Hkv*D]
            page_idx = jnp.repeat(page_ids, ps)
            off = jnp.tile(jnp.arange(ps), n)
            k_pages = _write_rows(k_pages, k_new, page_idx, off)
            v_pages = _write_rows(v_pages, v_new, page_idx, off)
            return k_pages, v_pages

        return adopt

    def _program(self, name: str, fn, **jit_kw):
        """``fn`` jitted under ``name`` (a program is named after its
        function, and the profile shows it as jit_<name>: stable names,
        for whoever reads a trace), its first call an ``engine.compile``
        lifecycle span."""
        return jax_env.FirstCallSpan(
            self._jax.jit(_named(name, fn), **jit_kw), "engine.compile",
            name, before=self._note_compile)

    def _note_compile(self, program: str):
        """A program first called after a decode step has emitted compiles
        under load: the requests in flight wait for it.  (The first
        request's own decode program follows its prefill's token, and is
        set-up still.)"""
        if self._stats["steps"]:
            logger.warning(
                "LLMEngine compiles %s after %d decode steps: every "
                "request in flight waits for it (warm each prompt bucket "
                "before taking traffic)", program, self._stats["steps"])

    def _prefill_fn(self, bucket: int):
        """Full-context prefill (empty cache): one program per pow2
        bucket."""
        key = ("full", bucket)
        fn = self._prefills.get(key)
        if fn is not None:
            return fn
        jax, jnp = self._jax, self._jnp
        model = self._model
        L, ps = self.num_layers, self.page_size
        from ray_tpu.serve.sampling import sample_tokens_with_logprobs

        record = self.record_experts
        ragged = self._ragged
        cmap = self._cmap

        def prefill(params, k_pages, v_pages, row, tokens, p, temp, top_p,
                    seed, slot=None, state=None):
            """tokens: [bucket] ids padded past p; row: [pp] page table
            row.  Returns updated pages + the sampled next token (the
            token at absolute position p, key fold_in(seed, p)) and its
            behavior logprob.  With ``state`` (a model that carries
            recurrent state): also the state, ``slot``'s set to what the
            prompt leaves behind (the padding past p advances nothing),
            and the head taken at row p - 1 only (so too for a model whose
            prefills are told their real rows, ``_ragged``: its buckets
            reach 16k rows, and its padding chooses no expert).  Last, where the engine records
            them: the bucket's rows' chosen experts."""
            ids = tokens[None]
            positions = jnp.arange(bucket)[None]
            sown = None
            with jax.named_scope("attend"):
                kw = {} if state is None and not ragged else {
                    "lengths": jnp.reshape(p, (1,)),
                    "logits_at": jnp.reshape(p - 1, (1,))}
                out = model.apply(
                    {"params": params}, ids, positions,
                    [_attend_uncached] * L,
                    mutable=["moe"] if record else False, **kw)
                if record:
                    out, sown = out
                if state is None:
                    logits, new_kvs = out
                    last = logits[0] if ragged else logits[0, p - 1][None]
                else:
                    logits, new_kvs, left = out
                    last = logits[0]
                    state = [{k: held[k].at[slot].set(
                        new[k][0].astype(held[k].dtype)) for k in held}
                        for held, new in zip(state, left)]
            with jax.named_scope("sample"):
                toks, logps = sample_tokens_with_logprobs(
                    last, jnp.reshape(p, (1,)),
                    jnp.reshape(temp, (1,)), jnp.reshape(top_p, (1,)),
                    jnp.reshape(seed, (1,)))
                next_tok, next_logp = toks[0], logps[0]
            with jax.named_scope("scatter"):
                if cmap is not None:  # what the model says the cache keeps
                    k_pages, v_pages = _write_kept(cmap, k_pages, v_pages,
                                                   row, p, new_kvs)
                else:
                    t = jnp.arange(bucket)
                    page_idx = jnp.where(t < p, row[t // ps], 0)
                    off = t % ps
                    # a layer's rows: [bucket, Hkv, D]
                    k_pages = _write_new(
                        k_pages, [nk[0][0] for nk in new_kvs], page_idx, off)
                    v_pages = _write_new(
                        v_pages, [None if nk[1] is None else nk[1][0]
                                  for nk in new_kvs], page_idx, off)
            out = (k_pages, v_pages, next_tok, next_logp)
            if state is not None:
                out += (state,)
            return out + (_experts_chosen(sown),) if record else out

        fn = self._program(f"llm_prefill_{bucket}", prefill,
                           # pools, and the state (the eleventh argument)
                           donate_argnums=(1, 2) if self._state is None
                           else (1, 2, 10))
        self._prefills[key] = fn
        return fn

    def _tail_prefill_fn(self, bucket: int):
        """Cache-aware tail prefill: the first ``start`` tokens' KV is
        already in the slot's pages (adopted from the prefix cache), so
        only the tail runs through the model — the tail tokens attend to
        the cache prefix plus themselves.  One program per pow2 tail
        bucket.  The one place that still gathers a dense view, of this
        one slot's row (``max_ctx`` rows, not the pool): a bucket of
        queries against one row is prefill-shaped work for
        ``cached_attention``, not the decode kernel's."""
        if self._state is not None:
            raise ValueError(
                "a tail prefill cannot serve a model with per-slot "
                "recurrent state: the cached prefix holds K/V and no "
                "state snapshot to start the tail from")
        if self._sparse:
            raise ValueError(
                "a tail prefill cannot serve a model with learned sparse "
                "attention: it attends to every row of the cached prefix")
        if self._cmap is not None:
            raise ValueError(
                "a tail prefill cannot serve a model whose cached rows are "
                "not its tokens: it reads the prefix as one row a position")
        key = ("tail", bucket)
        fn = self._prefills.get(key)
        if fn is not None:
            return fn
        jax, jnp = self._jax, self._jnp
        model = self._model
        L, ps, pp = self.num_layers, self.page_size, self.pages_per_slot
        from ray_tpu.ops.attention import cached_attention
        from ray_tpu.serve.sampling import sample_tokens_with_logprobs

        def gather(pages, row):  # → [L, 1, max_ctx, Hkv, D]
            rows = pages[:, row, :, :self.kv_heads * self.head_dim]
            return rows.reshape(L, 1, self.max_ctx, self.kv_heads,
                                self.head_dim)

        def tail_prefill(params, k_pages, v_pages, row, tokens, start, p,
                         temp, top_p, seed):
            """tokens: [bucket] tail ids (absolute positions start..p-1)
            padded past p-start; returns updated pages + the sampled
            next token at absolute position p and its behavior logprob."""
            with jax.named_scope("gather"):
                k_cache = gather(k_pages, row)
                v_cache = gather(v_pages, row)
                attend = [functools.partial(
                    cached_attention, k_cache=k_cache[i], v_cache=v_cache[i],
                    cache_lengths=jnp.reshape(start, (1,)))
                    for i in range(L)]
            positions = (start + jnp.arange(bucket))[None]
            with jax.named_scope("attend"):
                logits, new_kvs = model.apply(
                    {"params": params}, tokens[None], positions, attend)
            tail_len = p - start
            with jax.named_scope("sample"):
                toks, logps = sample_tokens_with_logprobs(
                    logits[0, tail_len - 1][None], jnp.reshape(p, (1,)),
                    jnp.reshape(temp, (1,)), jnp.reshape(top_p, (1,)),
                    jnp.reshape(seed, (1,)))
                next_tok, next_logp = toks[0], logps[0]
            with jax.named_scope("scatter"):
                t = jnp.arange(bucket)
                abs_pos = start + t
                page_idx = jnp.where(
                    t < tail_len, row[jnp.minimum(abs_pos // ps, pp - 1)],
                    0)
                off = abs_pos % ps
                newk = jnp.stack([nk[0][0] for nk in new_kvs])
                newv = jnp.stack([nk[1][0] for nk in new_kvs])
                k_pages = _write_rows(k_pages, newk, page_idx, off)
                v_pages = _write_rows(v_pages, newv, page_idx, off)
            return k_pages, v_pages, next_tok, next_logp

        fn = self._program(f"llm_tail_prefill_{bucket}", tail_prefill,
                           donate_argnums=(1, 2))
        self._prefills[key] = fn
        return fn

    def _draft_prefill_fn(self, bucket: int):
        """Draft-model full prefill (KV only, no sampling): in spec mode
        every admission warms the draft cache for the whole context —
        the draft is tiny by construction, so this is the cheap price of
        keeping the prefix cache and the KV wire draft-agnostic."""
        key = ("draft", bucket)
        fn = self._prefills.get(key)
        if fn is not None:
            return fn
        jax, jnp = self._jax, self._jnp
        model = self._draft_model
        L, ps = _kv_layers(model), self.page_size

        def prefill(params, k_pages, v_pages, row, tokens, p):
            ids = tokens[None]
            positions = jnp.arange(bucket)[None]
            _, new_kvs = model.apply(
                {"params": params}, ids, positions, [_attend_uncached] * L)
            t = jnp.arange(bucket)
            page_idx = jnp.where(t < p, row[t // ps], 0)
            off = t % ps
            newk = jnp.stack([nk[0][0] for nk in new_kvs])
            newv = jnp.stack([nk[1][0] for nk in new_kvs])
            k_pages = _write_rows(k_pages, newk, page_idx, off)
            v_pages = _write_rows(v_pages, newv, page_idx, off)
            return k_pages, v_pages

        fn = self._program(f"llm_draft_prefill_{bucket}", prefill,
                           donate_argnums=(1, 2))
        self._prefills[key] = fn
        return fn

    def _bucket_for(self, p: int) -> int:
        b = 8
        while b < p:
            b <<= 1
        return min(b, self.max_ctx)

    # ------------------------------------------------------------------
    # engine loop (one flow.Stage sink worker owns the device state)
    # ------------------------------------------------------------------
    def _tick_source(self):
        while True:
            with self._cond:
                if self._closed:
                    return
            if self._stage is not None and self._stage.token.cancelled:
                return
            yield None

    def _nothing_to_do(self) -> bool:
        return not (self._closed or self._pending or self._awaiting
                    or self._ready or self._pending_swaps
                    or self._active.any() or self._inflight is not None)

    def _iteration(self, _tick):
        """One pass of the loop thread.  Its spans (the names are a
        contract, PERF.md lists them with their readers) say what the
        host does between two device programs: ``engine.idle`` while
        there is nothing to do, then ``engine.iteration`` around
        ``engine.swap``, ``engine.admit`` (with one ``engine.prefill``
        per local prefill), ``engine.grow``, ``engine.decode.prepare``,
        ``engine.decode.dispatch`` (its parts ``engine.decode.stage``,
        ``.call`` and ``.readback``), ``engine.decode.settle``,
        ``engine.decode.fetch``, ``engine.emit`` and
        ``engine.metrics_flush``.  In the plain loop the dispatch is
        step n+1's and the fetch and emit are step n's
        (``_decode_once``).  As it closes, the iteration's span is told
        the thread's own CPU time over it (``cpu_ms``: the rest of its
        length the thread waited, for the device in
        ``engine.decode.fetch`` and for the interpreter anywhere) and the
        handle calls this process answered since the last one closed
        (``reply_calls``)."""
        with self._cond:
            if self._nothing_to_do():
                with obs.span("engine.idle"):
                    while self._nothing_to_do():
                        self._cond.wait(0.2)
                        if self._stage is not None and \
                                self._stage.token.cancelled:
                            return
            if self._closed:
                return
        with obs.span("engine.iteration", active=len(self._slot_req),
                      pending=len(self._pending)) as it:
            recording = it is not obs.NO_SPAN
            cpu0 = time.thread_time() if recording else 0.0
            t_work0 = time.perf_counter()
            try:
                # token boundary: between decode steps
                if self._pending_swaps:
                    # A token keeps the version that computed it.
                    self._drain()
                    with obs.span("engine.swap") as sp:
                        self._apply_swaps()
                        sp.set(version=self._weight_version)
                if self._pending or self._awaiting or self._ready:
                    before = self._stats["admitted"]
                    with obs.span("engine.admit") as sp:
                        self._poll_prefill()
                        self._admit()
                        sp.set(admitted=self._stats["admitted"] - before)
                with obs.span("engine.grow") as sp:
                    sp.set(pages=self._grow())
                if self._spec:
                    if self._active.any():
                        self._decode_once_spec()
                        self._step_stamps.append(time.monotonic())
                elif self._active.any() or self._inflight is not None:
                    self._decode_once()
            except BaseException as e:  # noqa: BLE001 — fail loudly per req
                self._fail_all(e)
                return
            self._work_s += time.perf_counter() - t_work0
            self._flush_metrics()
            calls = self._reply_calls
            if recording:
                it.set(cpu_ms=(time.thread_time() - cpu0) * 1e3,
                       reply_calls=calls - self._reply_calls_seen)
            self._reply_calls_seen = calls

    # ------------------------------------------------------------------
    # hot weight swap (loop thread only)
    # ------------------------------------------------------------------
    def _apply_swaps(self):
        """Install every queued weight version, newest last.  Runs
        between decode steps — the definition of a token boundary."""
        while True:
            with self._lock:
                if not self._pending_swaps:
                    return
                params, version, applied = self._pending_swaps.popleft()
            t0 = time.monotonic()
            try:
                params = self._resolve_swap_params(params)
                self._check_swap_tree(params)
            except BaseException:
                # The loop is about to die (_fail_all); wake the blocked
                # swapper NOW — its version check converts the wake into
                # a typed EngineClosedError instead of a full timeout.
                applied.set()
                raise
            # ONE device_put per version; the old arrays free once the
            # next compiled call stops referencing them.
            self._params = self._jax.device_put(params)
            self._weight_version = int(version)
            from ray_tpu.serve import prefix_cache as pc

            self._namespace = pc.versioned_namespace(
                self._base_namespace, self._weight_version)
            # In-flight requests: recycle through recompute preemption so
            # their KV is rebuilt under the new weights at re-admission
            # (sampled tokens are data; seeded sampling is position-
            # keyed, so the resumed stream continues seamlessly).
            for slot in range(self.max_slots):
                if self._active[slot]:
                    self._preempt(slot)
                    self._stats["swap_reprefills"] += 1
            self._stats["swaps"] += 1
            self._swap_latency_sum += time.monotonic() - t0
            applied.set()

    def _resolve_swap_params(self, params):
        try:
            import ray_tpu

            if isinstance(params, ray_tpu.ObjectRef):
                return ray_tpu.get(params)
        except Exception:
            pass
        return params

    def _check_swap_tree(self, params):
        """A silently mismatched tree would recompile the decode step
        (breaking the decode_cache_size==1 contract) or garble the
        model — fail loudly instead."""
        jax = self._jax
        new_leaves = jax.tree_util.tree_structure(params)
        cur_leaves = jax.tree_util.tree_structure(self._params)
        if new_leaves != cur_leaves:
            raise ValueError(
                "swap_weights params tree does not match the serving "
                f"model's ({new_leaves} vs {cur_leaves})")
        for new, cur in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(self._params)):
            if tuple(new.shape) != tuple(cur.shape) or \
                    new.dtype != cur.dtype:
                raise ValueError(
                    f"swap_weights leaf mismatch: {new.shape}/{new.dtype} "
                    f"vs serving {cur.shape}/{cur.dtype} — a swap must "
                    "not change shapes or dtypes (it would recompile)")

    def _fail_all(self, e: BaseException):
        with self._lock:
            self._closed = True  # a dead loop must reject new submits
            self._awaiting = []
            self._ready.clear()
            swaps = list(self._pending_swaps)
            self._pending_swaps.clear()
        self._inflight = None
        for _, _, applied in swaps:
            applied.set()
        for req in list(self._requests.values()):
            if not req.done.is_set():
                req.finish(error=e)
        for s in range(self.max_slots):
            if self._slot_pages[s]:
                self.pool.free(self._slot_pages[s])
                self._slot_pages[s] = []
        self._active[:] = False

    # ------------------------------------------------------------------
    # admission: prefix-cache lookup, local prefill or remote offload
    # ------------------------------------------------------------------
    def _admit(self):
        """Token-boundary admission: activate completed remote prefills
        first, then fill free slots from the pending queue, one prefill
        each.  Requires prompt pages + 1 free so the first decode token
        can't immediately force a preemption.  Offload decisions happen
        BEFORE any slot or page is reserved — a long-prompt burst
        streams out to the prefill replicas immediately and interactive
        requests behind it admit without waiting."""
        self._activate_ready()
        while True:
            with self._lock:
                if not self._pending:
                    return
                req = self._pending[0]
                ctx = req.context()
                p = len(ctx)
                cols = self._admission_columns(p)
                need = len(cols)
                if need + 1 > self.pool.capacity:
                    # Can never fit, even with the whole pool to itself —
                    # waiting would busy-spin forever.
                    self._pending.popleft()
                    req.finish(error=KVPoolExhaustedError(
                        f"request {req.id} needs {need + 1} pages but the "
                        f"pool holds {self.pool.capacity}"))
                    continue
                inflight = len(self._awaiting) + len(self._ready)
            if (self._prefill_client is not None
                    and inflight < self._prefill_max_inflight):
                # Uncached tail from the LOCAL cache view only (a
                # directory round trip at submit time would serialize
                # admissions; remote hits engage at activation).
                start = self._local_prefix_run(ctx)
                if p - start >= self._prefill_min:
                    job = self._prefill_client.submit(ctx, start,
                                                      req.sampling)
                    with self._lock:
                        self._pending.popleft()
                        self._awaiting.append((req, job, start))
                    self._left_queue(req)
                    self._stats["prefill_offloaded"] += 1
                    continue
            with self._lock:
                free = [s for s in range(self.max_slots)
                        if not self._active[s]]
                if not free:
                    return
                pages = self.pool.alloc(need + 1)
                if pages is None:
                    return  # pool too tight right now; retry next boundary
                self.pool.free(pages[need:])  # only reserve the +1 headroom
                pages = pages[:need]
                self._pending.popleft()
                slot = free[0]
                mid_batch = bool(self._active.any())
            self._left_queue(req)
            self._slot_pages[slot] = pages
            row = np.zeros((self.pages_per_slot,), np.int32)
            row[cols] = pages
            self._table[slot] = row
            # Longest cached prefix: adopt its pages, prefill the tail.
            cached = self._lookup_prefix(ctx)
            start = len(cached) * self.page_size
            if cached:
                self._adopt_pages(slot, 0, cached)
                self._stats["prefill_tokens_saved"] += start
            nxt, lp = self._local_prefill(slot, req, ctx, start)
            self._finish_admission(slot, req, p, nxt, lp, mid_batch)

    def _admission_columns(self, p: int) -> List[int]:
        """The columns of a slot's table row that hold a page once a
        context of ``p`` rows is cached: the first ``ceil(p / page_size)``,
        or what the model's map says (summary pages, then ring pages)."""
        if self._cmap is None:
            return list(range(math.ceil(p / self.page_size)))
        summaries, ring = self._cmap.owned(p - 1)
        first = self._cmap.summary_pages
        return list(range(summaries)) + list(range(first, first + ring))

    def _column_missing(self, slot: int, last: int) -> Optional[int]:
        """The column of the slot's table row that wants a page before
        position ``last`` can be written; None when it owns them all.  A
        slot's pages grow a position at a time, so under a map only the
        last column of either part can be missing (page 0 is the scratch
        page: no slot owns it)."""
        if self._cmap is None:
            owned = len(self._slot_pages[slot])
            return owned if last // self.page_size >= owned else None
        summaries, ring = self._cmap.owned(last)
        for col in (summaries - 1, self._cmap.summary_pages + ring - 1):
            if not self._table[slot, col]:
                return col
        return None

    def _left_queue(self, req: _Request):
        """``_admit`` has just taken ``req`` off ``_pending``: the end of
        its ``request.queued`` span, and of the wait that
        ``_finish_admission`` hands the ``serve_queue_wait_s`` histogram
        (not here: the histogram's round trip to the head would hold up
        the prefill's dispatch, right after an emit woke every reader)."""
        now = time.perf_counter()
        req.queue_wait = now - req.submitted
        obs.record("request.queued", req.submitted, now, ctx=req.trace_ctx,
                   request_id=req.id, prompt_tokens=len(req.prompt),
                   pending_ahead=req.pending_ahead)

    def _local_prefix_run(self, ctx: List[int]) -> int:
        """Length (tokens) of the leading full-page run present in the
        LOCAL cache — contains() only, no fetch, no directory RPC."""
        if self._prefix is None:
            return 0
        from ray_tpu.serve import prefix_cache as pc

        keys = pc.prefix_page_keys(
            self._namespace, ctx, self.page_size,
            max_pages=(len(ctx) - 1) // self.page_size)
        n = 0
        for key in keys:
            if not self._prefix.contains(key):
                break
            n += 1
        return n * self.page_size

    def _activate_ready(self):
        """Admit completed remote prefills into free slots: allocate the
        slot + pages now, re-adopt the cached prefix, adopt the streamed
        tail pages, activate.  If the prefix was evicted during the
        round trip (rare), fall back to a full local prefill — the tail
        payload alone can't cover the missing positions."""
        while self._ready:
            req, result, start = self._ready[0]
            ctx = req.context()
            p = len(ctx)
            need = math.ceil(p / self.page_size)
            with self._lock:
                free = [s for s in range(self.max_slots)
                        if not self._active[s]]
                if not free:
                    return
                pages = self.pool.alloc(need + 1)
                if pages is None:
                    return
                self.pool.free(pages[need:])
                pages = pages[:need]
                slot = free[0]
                mid_batch = bool(self._active.any())
                self._ready.popleft()
            self._slot_pages[slot] = pages
            row = np.zeros((self.pages_per_slot,), np.int32)
            row[:need] = pages
            self._table[slot] = row
            k_np, v_np, next_tok, meta = result
            first_page = start // self.page_size
            if start:
                cached = self._lookup_prefix(ctx, max_pages=first_page)
                if len(cached) < first_page:
                    self._stats["prefill_prefix_fallback"] += 1
                    hit = len(cached) * self.page_size
                    if cached:
                        self._adopt_pages(slot, 0, cached)
                        self._stats["prefill_tokens_saved"] += hit
                    nxt, lp = self._local_prefill(slot, req, ctx, hit)
                    self._finish_admission(slot, req, p, nxt, lp, mid_batch)
                    continue
                self._adopt_pages(slot, 0, cached)
                self._stats["prefill_tokens_saved"] += start
            self._adopt_pages(
                slot, first_page,
                [(k_np[:, j], v_np[:, j]) for j in range(k_np.shape[1])])
            self._stats["wire_bytes"] += int(meta.get("wire_bytes", 0))
            self._stats["wire_fp32_bytes"] += int(meta.get("fp32_bytes", 0))
            if meta.get("exact", True):
                self._publish_prefix(ctx, slot)
            self._finish_admission(slot, req, p, int(next_tok),
                                   float(meta.get("next_logp", float("nan"))),
                                   mid_batch)

    def _local_prefill(self, slot: int, req: _Request, ctx: List[int],
                       start: int):
        """Run the (full or cache-aware tail) prefill into the slot's
        pages and wait for it; returns (sampled next token, its behavior
        logprob)."""
        p = len(ctx)
        row = self._table[slot]
        s = req.sampling
        tail_len = p - start
        self._stats["prefill_tokens"] += tail_len
        bucket = self._bucket_for(tail_len)
        # scanned_rows / padded_rows: what a recurrent scan ran over, the
        # prompt's real rows and the bucket's padding that advanced nothing
        # selecting_rows: real rows at positions from index_topk on, whose
        # attention runs over a selection and not over every earlier row
        scanned = {} if self._state is None else {
            "scanned_rows": tail_len, "padded_rows": bucket - tail_len}
        if self._sparse:
            scanned["selecting_rows"] = max(
                0, p - self._model.config.index_topk)
        if self._cmap is not None:
            # what the prefill hands the cache: the windows its real rows
            # span, the open window's rows and the whole chunks' summaries
            m = self._cmap
            scanned.update(windows=-(-p // m.window),
                           window_rows=int(m.window_rows(p)),
                           summary_rows=p // m.chunk)
        with obs.span("engine.prefill", request_id=req.id, bucket=bucket,
                      prompt_tokens=p, cached_tokens=start, **scanned):
            toks = np.zeros((bucket,), np.int32)
            toks[:tail_len] = ctx[start:]
            if start == 0:
                fn = self._prefill_fn(bucket)
                args = (self._params, self._k_pages, self._v_pages, row,
                        toks, np.int32(p), np.float32(s.temperature),
                        np.float32(s.top_p), np.int32(s.seed))
                if self._state is None:
                    self._k_pages, self._v_pages, nxt, lp, *chosen = fn(
                        *args)
                else:
                    (self._k_pages, self._v_pages, nxt, lp, self._state,
                     *chosen) = fn(*args, np.int32(slot), self._state)
                if req.record_experts:  # anew: a re-admission recomputes
                    req.fed_experts = list(
                        np.asarray(chosen[0])[:, :p].transpose(1, 0, 2))
                    req.fed_selected = []
            else:
                fn = self._tail_prefill_fn(bucket)
                self._k_pages, self._v_pages, nxt, lp = fn(
                    self._params, self._k_pages, self._v_pages, row, toks,
                    np.int32(start), np.int32(p), np.float32(s.temperature),
                    np.float32(s.top_p), np.int32(s.seed))
            self._publish_prefix(ctx, slot)
            return int(nxt), float(lp)

    def _finish_admission(self, slot: int, req: _Request, p: int,
                          next_tok: int, next_logp: float, mid_batch: bool):
        """Shared tail of every admission path: the slot's KV covers
        positions [0, p) and ``next_tok`` is the sampled token at p."""
        if self._spec:
            self._warm_draft(slot, req.context())
        s = req.sampling
        self._stats["admitted"] += 1
        if mid_batch:
            self._stats["admitted_mid_batch"] += 1
        self._observe_queue_wait(req.queue_wait)
        if not req.first_token:
            req.first_token = time.perf_counter()
        self._lengths[slot] = p
        self._last_tok[slot] = next_tok
        self._temps[slot] = s.temperature
        self._top_ps[slot] = s.top_p
        self._seeds[slot] = s.seed
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        with self._lock:
            self._active[slot] = True
        self._slot_req[slot] = req
        self._append_token(slot, req, next_tok, next_logp)
        self._budget[slot] = req.max_new_tokens - len(req.out)
        self._fresh[slot] = True

    def _warm_draft(self, slot: int, ctx: List[int]):
        """Spec mode: full draft prefill of the context into the draft
        page arrays (same table row as the target)."""
        p = len(ctx)
        bucket = self._bucket_for(p)
        toks = np.zeros((bucket,), np.int32)
        toks[:p] = ctx
        fn = self._draft_prefill_fn(bucket)
        self._dk_pages, self._dv_pages = fn(
            self._draft_params, self._dk_pages, self._dv_pages,
            self._table[slot], toks, np.int32(p))

    # ------------------------------------------------------------------
    # prefix cache: lookup / adopt / publish
    # ------------------------------------------------------------------
    def _lookup_prefix(self, ctx: List[int],
                       max_pages: Optional[int] = None) -> List[tuple]:
        """(k, v) host arrays for the longest cached run of leading full
        pages — local LRU first, then the cluster directory (refs
        fetched with one get_many and written through to the local
        cache).  Capped at (len-1)//page_size so at least one position
        is always freshly computed (the sampled next token needs a
        logits row).  Never raises: a broken directory is a miss."""
        if self._prefix is None:
            return []
        from ray_tpu.serve import prefix_cache as pc

        p = len(ctx)
        cap = (p - 1) // self.page_size
        if max_pages is not None:
            cap = min(cap, max_pages)
        keys = pc.prefix_page_keys(self._namespace, ctx, self.page_size,
                                   max_pages=cap)
        out: List[tuple] = []
        miss_at = len(keys)
        for i, key in enumerate(keys):
            entry = self._prefix.get(key)
            if entry is None:
                miss_at = i
                break
            out.append(entry)
        if out:
            self._stats["prefix_hit_pages"] += len(out)
        if miss_at >= len(keys) or self._directory is None:
            return out
        try:
            import ray_tpu

            rest = keys[miss_at:]
            entries = ray_tpu.get(
                self._directory.lookup_many.remote(rest),
                timeout=self._directory_timeout)
            run = []
            for e in entries:
                if e is None:
                    break
                run.append(e)
            if not run:
                return out
            refs = [r for e in run for r in e]
            vals = ray_tpu.get_many(refs, timeout=self._directory_timeout)
            for j in range(len(run)):
                k_np, v_np = vals[2 * j], vals[2 * j + 1]
                self._prefix.put(rest[j], k_np, v_np)
                out.append((k_np, v_np))
            self._stats["prefix_hit_pages"] += len(run)
            self._stats["prefix_remote_hit_pages"] += len(run)
        except Exception:
            pass  # the cache is an optimization, never a failure source
        return out

    def _adopt_pages(self, slot: int, first_page: int, pages: List[tuple]):
        """Scatter host (k, v) page arrays into the slot's device pages
        starting at page index ``first_page`` (one fixed-shape compiled
        scatter; unused rows route to scratch)."""
        n = len(pages)
        if n == 0:
            return
        ids = np.zeros((self.pages_per_slot,), np.int32)
        ids[:n] = self._table[slot, first_page:first_page + n]
        bk, bv = self._adopt_buf_k, self._adopt_buf_v
        hd = self.kv_heads * self.head_dim
        for j, (k_np, v_np) in enumerate(pages):  # [L, ps, Hkv, D] each
            bk[:, j, :, :hd] = k_np.reshape(bk.shape[0], self.page_size, hd)
            bv[:, j, :, :hd] = v_np.reshape(bv.shape[0], self.page_size, hd)
        bk[:, n:] = 0
        bv[:, n:] = 0
        self._k_pages, self._v_pages = self._adopt(
            self._k_pages, self._v_pages, ids, bk, bv)

    def _publish_prefix(self, ctx: List[int], slot: int):
        """Snapshot every full page of ``ctx`` into the local LRU and
        (when a directory is attached) the object plane.  Pages are
        immutable once full — the snapshot is a host copy, later decode
        writes touch later pages."""
        if self._prefix is None:
            return
        from ray_tpu.serve import prefix_cache as pc

        p = len(ctx)
        n_full = p // self.page_size
        if n_full == 0:
            return
        keys = pc.prefix_page_keys(self._namespace, ctx, self.page_size,
                                   max_pages=n_full)
        to_publish = []
        for i, key in enumerate(keys):
            if self._prefix.contains(key):
                continue
            page_id = int(self._table[slot, i])
            # The cache and the wire keep a page as [L, ps, Hkv, D].
            page = (self.num_layers, self.page_size, self.kv_heads,
                    self.head_dim)
            hd = self.kv_heads * self.head_dim
            k_np = np.asarray(self._k_pages[:, page_id, :, :hd]).reshape(page)
            v_np = np.asarray(self._v_pages[:, page_id, :, :hd]).reshape(page)
            self._prefix.put(key, k_np, v_np)
            self._stats["prefix_published_pages"] += 1
            to_publish.append((key, k_np, v_np))
        if self._directory is None or not to_publish:
            return
        try:
            import ray_tpu

            arrays = [a for _, k_np, v_np in to_publish
                      for a in (k_np, v_np)]
            refs = ray_tpu.put_many(arrays)
            for j, (key, _, _) in enumerate(to_publish):
                k_ref, v_ref = refs[2 * j], refs[2 * j + 1]
                # Hold our refs across the publish handoff (bounded; the
                # directory is the durable holder once it pins them).
                self._published_refs[key] = (k_ref, v_ref)
                while len(self._published_refs) > 256:
                    self._published_refs.popitem(last=False)
                # Refs nested in a list: a top-level ref arg would
                # be materialized by the task runtime (see
                # PrefixDirectory.publish).
                self._directory.publish.remote(key, [k_ref, v_ref])
        except Exception:
            pass

    # ------------------------------------------------------------------
    # disaggregated prefill: poll + adopt streamed KV pages
    # ------------------------------------------------------------------
    def _poll_prefill(self):
        """Collect completed remote prefills into the ready queue; decode
        for already-active slots never waits on these, and activation
        happens at the next token boundary with a free slot."""
        with self._lock:
            awaiting = list(self._awaiting)
        for entry in awaiting:
            req, job, start = entry
            try:
                result = job.poll()
            except Exception as e:  # noqa: BLE001 — typed per-request fail
                with self._lock:
                    if entry in self._awaiting:
                        self._awaiting.remove(entry)
                req.finish(error=e)
                continue
            if result is None:
                continue
            with self._lock:
                self._awaiting.remove(entry)
                self._ready.append((req, result, start))

    # ------------------------------------------------------------------
    # decode steps
    # ------------------------------------------------------------------
    def _grow(self):
        """Allocate pages for every slot in the next step whose write
        horizon crosses a page boundary; preempt the youngest other
        request when the pool is dry (vLLM-style recompute preemption).
        The horizon is one token past ``_lengths`` (which the plain loop
        advances as it dispatches, so this looks one step further than
        the tokens the host has seen), or ``spec_tokens`` positions in
        spec mode (the verify step scatters the whole window).  Returns
        the pages it gave out."""
        horizon = self.spec_tokens if self._spec else 1
        given = 0
        for slot in range(self.max_slots):
            while self._active[slot] and self._budget[slot] > 0:
                pos = int(self._lengths[slot])
                last = min(pos + horizon - 1, self.max_ctx - 1)
                col = self._column_missing(slot, last)
                if col is None:
                    break
                got = self.pool.alloc(1)
                if got is not None:
                    self._table[slot, col] = got[0]
                    self._slot_pages[slot].append(got[0])
                    given += 1
                    continue
                if self._inflight is not None:
                    # A dry pool: be in step with the device before any
                    # request is put back, and the step in flight may
                    # itself retire one and free its pages.
                    self._drain()
                    continue
                victim = self._pick_victim(exclude=slot)
                if victim is None:
                    req = self._slot_req[slot]
                    self._retire(slot, req, error=KVPoolExhaustedError(
                        f"request {req.id} needs page "
                        f"{len(self._slot_pages[slot]) + 1} "
                        f"but the pool ({self.pool.capacity} pages) is "
                        f"exhausted and no other request can be "
                        f"preempted"))
                    break
                self._preempt(victim)
        return given

    def _pick_victim(self, exclude: int) -> Optional[int]:
        best, best_seq = None, -1
        for s in range(self.max_slots):
            if s == exclude or not self._active[s]:
                continue
            seq = self._slot_req[s].admit_seq
            if seq > best_seq:
                best, best_seq = s, seq
        return best

    def _preempt(self, slot: int):
        req = self._slot_req.pop(slot)
        self.pool.free(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._table[slot] = 0
        self._lengths[slot] = 0
        self._temps[slot], self._top_ps[slot] = 0.0, 1.0  # as in _retire
        self._stats["preemptions"] += 1
        req.preemptions += 1
        req.submitted = time.perf_counter()  # queued again, from now
        with self._lock:
            self._active[slot] = False
            self._pending.appendleft(req)  # readmitted first, from context()

    def _count_sampling_rows(self) -> int:
        """Slots whose row asks this step's sampler for a draw (freed
        slots are reset to greedy, so these are active ones): 0 means the
        step's programs take the argmax and nothing else."""
        n = int(np.count_nonzero(self._temps > 0.0))
        self._stats["sampled_steps" if n else "greedy_steps"] += 1
        return n

    def _decode_once(self):
        """One turn of the plain decode loop, which keeps one step in
        flight: step n+1 is dispatched before step n is read, so the
        read (a wait for copies started at dispatch), the emit and
        whatever the loop does before it comes back here (metrics,
        admission, page growth) run under a running program.  Nothing in
        step n+1's inputs needs step n on the host: its tokens are on the
        device, its lengths the host advances itself, and
        ``max_new_tokens`` ends a request before the dispatch.  Only an
        ``eos_id`` is seen a step late (``_collect`` drops that row)."""
        ahead = self._dispatch_step()
        self._drain()
        self._inflight = ahead

    def _drain(self):
        """Be in step with the device: read and emit the step in flight,
        if any.  Before a weight swap, and before a dry pool preempts."""
        if self._inflight is not None:
            self._collect()

    def _on_device(self, name: str, host: np.ndarray):
        """The device's copy of an array of slot state.  It stays there
        between steps and is sent again only when the host's value is no
        longer the one it holds (admission, retirement, page growth)."""
        held = self._resident.get(name)
        if held is None or not np.array_equal(held[1], host):
            value = host.copy()  # the host's array changes in place
            held = self._resident[name] = (self._jax.device_put(value),
                                           value)
            self._uploads += 1
            self._upload_bytes += value.nbytes
        return held[0]

    def _dispatch_step(self) -> Optional[_Step]:
        """Launch one decode step for the slots that still need a token;
        None when no slot does.  Four stretches of the loop thread, each
        a span: ``engine.decode.prepare`` (which rows, what they ask
        for), ``engine.decode.dispatch`` (``.stage``: the slot state the
        device does not hold yet; ``.call``: the program's enqueue;
        ``.readback``: the copies to the host set off) and
        ``engine.decode.settle`` (the pools and the state rebound, the
        donated ones let go; the host's mirrors; the step's list of
        rows)."""
        rows = self._active & (self._budget > 0)
        if not rows.any():  # no step, so no span: a profile keeps them all
            return None
        with obs.span("engine.decode.prepare"):
            in_flight = int(self._inflight is not None)
            self._stats[
                "lookahead_steps" if in_flight else "drained_steps"] += 1
            sampling_rows = self._count_sampling_rows()
            # kv_tokens: the cached rows this step's attention reads, which
            # is what the benchmark's paged_attn_roofline counts the bytes
            # of.  state_slots: the slots whose recurrent state the step
            # advances.  index_rows: the cached rows the step's indexers
            # score, every selecting layer's (a model with learned sparse
            # attention).
            stateful, moved = (), {}
            kv_tokens = int(self._lengths[rows].sum())
            if self._state is not None:
                stateful = (self._state,)
                moved = {"state_slots": int(rows.sum())}
                self._stats["state_slots_moved"] += moved["state_slots"]
            if self._sparse:
                moved["index_rows"] = kv_tokens * self.num_layers
                self._stats["dsa_rows_scored"] += moved["index_rows"]
            if self._cmap is not None:
                # the rows read are not the positions held (ctx_tokens):
                # summaries of the windows before and the open window's rows
                m, at = self._cmap, self._lengths[rows]
                moved.update(
                    ctx_tokens=kv_tokens,
                    summary_rows=int(m.summary_rows(at).sum()),
                    window_rows=int(m.window_rows(at).sum()),
                    chunks_closed=int(m.closes_chunk(at).sum()),
                    windows_closed=int(m.closes_window(at).sum()))
                kv_tokens = moved["summary_rows"] + moved["window_rows"]
                for key in ("ctx_tokens", "chunks_closed", "windows_closed"):
                    self._stats["cache_" + key] += moved[key]
                self._stats["cache_rows_read"] += kv_tokens
        with obs.span("engine.decode.dispatch", kv_tokens=kv_tokens,
                      sampling_rows=sampling_rows, in_flight=in_flight,
                      **moved):
            with obs.span("engine.decode.stage") as sp:
                dev = self._on_device
                sent, sent_bytes = self._uploads, self._upload_bytes
                staged = (
                    dev("table", self._table), dev("lengths", self._lengths),
                    dev("last_tok", self._last_tok), dev("active", rows),
                    dev("temps", self._temps), dev("top_ps", self._top_ps),
                    dev("seeds", self._seeds), self._prev_tok,
                    dev("fresh", self._fresh))
                sp.set(uploads=self._uploads - sent,
                       upload_bytes=self._upload_bytes - sent_bytes)
            with obs.span("engine.decode.call"):
                outs = self._decode(self._params, self._k_pages,
                                    self._v_pages, *staged, *stateful)
            k_pages, v_pages, nxt, lps, lengths, *touched = outs
            state = touched.pop() if stateful else None
            rows_read = touched.pop() if self._sparse else None
            # fetched only for a row whose request records them
            recording = self.record_experts
            selected = touched.pop() if recording and self._sparse else None
            chosen = touched.pop() if recording else None
            fetched = [out for out in (nxt, lps, rows_read, *touched)
                       if out is not None]
            with obs.span("engine.decode.readback", arrays=len(fetched)):
                for out in fetched:
                    out.copy_to_host_async()
        with obs.span("engine.decode.settle"):
            # The donated pools, and the donated state with this frame's
            # locals, die inside the span: letting go of a device array
            # takes the loop thread ~0.2 ms in a busy replica, and that
            # time has a name here.
            self._k_pages, self._v_pages = k_pages, v_pages
            if stateful:
                self._state = state
            del outs, staged, stateful, k_pages, v_pages, state
            self._lengths[rows] += 1  # as the program does: that K/V lands
            self._resident["lengths"] = (lengths, self._lengths.copy())
            self._budget[rows] -= 1
            self._fresh[:] = False
            self._prev_tok = nxt
            return _Step(nxt, lps, touched[0] if touched else None,
                         [(s, self._slot_req[s])
                          for s in np.flatnonzero(rows).tolist()], chosen,
                         rows_read, selected)

    def _collect(self):
        """Wait for the results of the step in flight and emit them.  The
        step is this frame's alone, so that its device arrays die where
        the last ``engine.decode.settle`` says."""
        step, self._inflight = self._inflight, None
        n_rows = len(step.rows)
        with obs.span("engine.decode.fetch") as sp:  # the host waits here
            nxt = np.asarray(step.tokens)
            lps = np.asarray(step.logps)
            if step.touched is not None:  # see _experts_touched
                hit, busiest, streamed, *local = (
                    int(v) for v in np.asarray(step.touched))
                choices = n_rows * self._moe_choices
                # every choice lands here where every expert is held
                landed = local[0] if local else choices
                sp.set(experts_hit=hit, experts_streamed=streamed,
                       experts_held=self._moe_experts,
                       local_choices=landed, choices=choices)
                self._stats["moe_experts_hit"] += hit
                self._stats["moe_experts_streamed"] += streamed
                self._stats["moe_local_choices"] += landed
                self._stats["moe_choices"] += choices
                self._moe_busiest_share_sum += busiest / choices
            if step.rows_read is not None:  # see _rows_read
                read = int(np.asarray(step.rows_read))
                sp.set(kv_rows_read=read)
                self._stats["dsa_rows_read"] += read
        with obs.span("engine.decode.settle"):
            self._stats["steps"] += 1
            self._occupancy_sum += n_rows / self.max_slots
            emitted = 0
            chosen = selected = None
            if any(req.record_experts for _, req in step.rows):
                chosen = np.asarray(step.chosen)  # [expert layers, slots, k]
                if step.selected is not None:  # [layers, slots, index_topk]
                    selected = np.asarray(step.selected)
        with obs.span("engine.emit") as sp:
            for slot, req in step.rows:
                if self._slot_req.get(slot) is not req:
                    # Its eos_id came in the step before, read after this
                    # one was dispatched.  The row's K/V went to a page the
                    # slot held then; whoever gets that page writes behind
                    # it on the device's one stream.
                    self._stats["late_eos_rows"] += 1
                    continue
                emitted += 1
                if req.record_experts:  # of the row this step fed
                    req.fed_experts.append(chosen[:, slot])
                    if selected is not None:
                        req.fed_selected.append(selected[:, slot])
                self._append_token(slot, req, int(nxt[slot]),
                                   float(lps[slot]))
            sp.set(tokens=emitted)
        with obs.span("engine.decode.settle"):
            self._stats["tokens"] += emitted
            self._step_stamps.append(time.monotonic())
            del step, nxt, lps, chosen, selected  # the step's arrays die here

    def _decode_once_spec(self):
        """Draft k-1 proposals per slot, verify the [slots, k] window in
        ONE target step, accept the longest matching prefix plus the
        target's correction token.  Because sampling keys depend only on
        (seed, absolute position), the emitted stream is bitwise the
        non-speculative stream — the draft only sets the tokens/step."""
        k = self.spec_tokens
        n_active = int(self._active.sum())
        self._count_sampling_rows()
        proposals = np.zeros((self.max_slots, k - 1), np.int32)
        d_last = self._last_tok.copy()
        for j in range(k - 1):
            with obs.span("engine.decode.dispatch"):
                self._dk_pages, self._dv_pages, nxt, *_ = \
                    self._draft_decode(
                        self._draft_params, self._dk_pages, self._dv_pages,
                        self._table, self._lengths + j, d_last,
                        self._active, self._temps, self._top_ps,
                        self._seeds)
            with obs.span("engine.decode.fetch"):
                d_last = np.asarray(nxt)
            proposals[:, j] = d_last
        # Catch-up step: write the LAST proposal's draft KV (position
        # len+k-1).  On full acceptance that position becomes part of
        # the valid cache next iteration, and without this write the
        # draft would read a stale row and desync; on partial
        # acceptance the row sits beyond kv_lengths and is overwritten
        # before it is ever read.  The sampled output is discarded.
        with obs.span("engine.decode.dispatch"):
            self._dk_pages, self._dv_pages, *_ = self._draft_decode(
                self._draft_params, self._dk_pages, self._dv_pages,
                self._table, self._lengths + (k - 1), d_last, self._active,
                self._temps, self._top_ps, self._seeds)
            window = np.concatenate(
                [self._last_tok[:, None], proposals], axis=1)
            self._k_pages, self._v_pages, sampled, v_logps = self._verify(
                self._params, self._k_pages, self._v_pages, self._table,
                self._lengths, window, self._active, self._temps,
                self._top_ps, self._seeds)
        with obs.span("engine.decode.fetch"):
            sampled = np.asarray(sampled)  # [slots, k]: at len+1..len+k
            v_logps = np.asarray(v_logps)
        self._stats["steps"] += 1
        self._stats["spec_steps"] += 1
        self._occupancy_sum += n_active / self.max_slots
        tokens0 = self._stats["tokens"]
        with obs.span("engine.emit") as sp:
            for slot in range(self.max_slots):
                if not self._active[slot]:
                    continue
                req = self._slot_req[slot]
                m = 0
                while m < k - 1 and proposals[slot, m] == sampled[slot, m]:
                    m += 1
                emit = m + 1  # matched proposals + the target's own token
                self._stats["spec_proposed"] += k - 1
                self._stats["spec_accepted"] += m
                req.spec_proposed += k - 1
                req.spec_accepted += m
                self._stats["tokens"] += emit
                self._lengths[slot] += emit
                self._last_tok[slot] = int(sampled[slot, emit - 1])
                for j in range(emit):
                    self._append_token(slot, req, int(sampled[slot, j]),
                                       float(v_logps[slot, j]))
                    if not self._active[slot]:
                        break  # retired mid-window (EOS / max_new_tokens)
            sp.set(tokens=self._stats["tokens"] - tokens0)

    def _append_token(self, slot: int, req: _Request, tok: int,
                      logp: float = float("nan")):
        req.out.append(tok)
        req.out_logps.append(logp)
        req.out_versions.append(self._weight_version)
        finished = (len(req.out) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id))
        if finished:
            self._retire(slot, req)
        elif len(req.out) - req.streamed >= self.chunk_tokens:
            req.chunks.put(req.out[req.streamed:])
            req.streamed = len(req.out)

    def _retire(self, slot: int, req: _Request,
                error: Optional[BaseException] = None):
        self.pool.free(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._table[slot] = 0
        self._lengths[slot] = 0
        # The sampler does what the step's rows ask for, all rows: a freed
        # slot asks for nothing, or one retired sampled request would hold
        # the nucleus pass open for the greedy ones after it.
        self._temps[slot], self._top_ps[slot] = 0.0, 1.0
        self._slot_req.pop(slot, None)
        with self._lock:
            self._active[slot] = False
            self._evict_consumed_locked()
        self._stats["completed"] += 1
        if req.first_token:
            obs.record("request.decode", req.first_token,
                       time.perf_counter(), ctx=req.trace_ctx,
                       request_id=req.id, tokens=len(req.out),
                       preemptions=req.preemptions)
        req.finish(error=error)

    def _evict_consumed_locked(self):
        """Bound the registry without losing undrained streams: only
        finished requests whose consumer has the terminal state
        (``consumed``) are dropped — a finished streaming request whose
        chunk queue hasn't been drained survives, so late ``next_chunk``
        pulls never lose tail chunks (regression: ISSUE 13)."""
        if len(self._requests) <= self.REGISTRY_LIMIT:
            return
        for rid in list(self._requests):
            if len(self._requests) <= self.REGISTRY_FLOOR:
                break
            r = self._requests[rid]
            if r.done.is_set() and r.consumed:
                del self._requests[rid]

    # ------------------------------------------------------------------
    # metrics (best-effort: the engine also runs without a ray runtime)
    # ------------------------------------------------------------------
    def _ensure_metrics(self):
        if self._metrics is None:
            from ray_tpu.util import metrics as um

            self._metrics = {
                "tokens": um.Meter("serve_tokens",
                                   "Tokens generated by the decode engine"),
                "requests": um.Meter("serve_requests",
                                     "Requests completed by the engine"),
                "inflight": um.Gauge("serve_inflight_requests",
                                     "Active + queued engine requests"),
                "occupancy": um.Gauge("serve_batch_occupancy",
                                      "Active slots / max_slots"),
                "pages_in_use": um.Gauge("serve_kv_pages_in_use",
                                         "KV cache pages allocated"),
                "pages_free": um.Gauge("serve_kv_pages_free",
                                       "KV cache pages free"),
                "tokens_per_s": um.Gauge("serve_tokens_per_s",
                                         "Engine decode throughput"),
                "prefix_hits": um.Meter(
                    "serve_prefix_hit_pages",
                    "KV pages adopted from the prefix cache"),
                "spec_accept": um.Gauge(
                    "serve_spec_acceptance",
                    "Speculative-decode acceptance rate (accepted / "
                    "proposed draft tokens)"),
                "queue_wait": um.Histogram(
                    "serve_queue_wait_s", "Submit-to-admission wait",
                    boundaries=(0.001, 0.01, 0.1, 1.0, 10.0)),
            }

    def _observe_queue_wait(self, wait_s: float):
        try:
            self._ensure_metrics()
            self._metrics["queue_wait"].observe(wait_s)
        except Exception:
            pass

    def _flush_metrics(self):
        now = time.monotonic()
        if now - self._metrics_flush < 2.0:
            return
        self._metrics_flush = now
        try:
            with obs.span("engine.metrics_flush"):
                self._ensure_metrics()
                m, st = self._metrics, self._stats
                m["tokens"].mark(st["tokens"] - m["tokens"].total())
                m["requests"].mark(st["completed"] - m["requests"].total())
                m["prefix_hits"].mark(
                    st["prefix_hit_pages"] - m["prefix_hits"].total())
                if st.get("spec_proposed", 0):
                    m["spec_accept"].set(
                        st["spec_accepted"] / st["spec_proposed"])
                with self._lock:
                    inflight = int(self._active.sum()) + len(self._pending)
                    occ = float(self._active.sum()) / self.max_slots
                m["inflight"].set(inflight)
                m["occupancy"].set(occ)
                pool = self.pool.stats()
                m["pages_in_use"].set(pool["in_use"])
                m["pages_free"].set(pool["free"])
                m["tokens_per_s"].set(st["tokens"] / max(1e-9,
                                                         now - self._t0))
                for meter in (m["tokens"], m["requests"], m["prefix_hits"]):
                    meter.flush()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# The naive per-request baseline and shared model builders
# ---------------------------------------------------------------------------
class NaiveLM:
    """Per-request serving baseline: batch-1, no KV cache — every token
    re-runs the full-context forward pass at a fixed padded width (one
    compile; padding is exact under the causal mask).  This is the
    reference the engine must be token-identical to.  ``sampling`` makes
    it the seeded-sampling reference too: it draws with the same
    ``fold_in(PRNGKey(seed), position)`` keys over full-context logits,
    so engine sampling must reproduce it bitwise."""

    def __init__(self, model, params, width: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.serve.sampling import sample_tokens

        self.params = params
        self.width = width

        def step(params, ids, n, temp, top_p, seed):
            logits = model.apply({"params": params}, ids)
            return sample_tokens(
                logits[0, n - 1][None], jnp.reshape(n, (1,)),
                jnp.reshape(temp, (1,)), jnp.reshape(top_p, (1,)),
                jnp.reshape(seed, (1,)))[0]

        self._step = jax.jit(step)

    def generate(self, prompt: Sequence[int], max_new_tokens: int,
                 eos_id: Optional[int] = None,
                 sampling: Optional[SamplingParams] = None) -> List[int]:
        s = sampling or GREEDY
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        buf = np.zeros((1, self.width), np.int32)
        buf[0, :len(prompt)] = prompt
        n = len(prompt)
        out: List[int] = []
        for _ in range(max_new_tokens):
            tok = int(self._step(self.params, buf, np.int32(n),
                                 np.float32(s.temperature),
                                 np.float32(s.top_p), np.int32(s.seed)))
            out.append(tok)
            if n < self.width:
                buf[0, n] = tok
            n += 1
            if eos_id is not None and tok == eos_id:
                break
        return out


def build_model(model_kind: str, config_kw: Optional[dict] = None,
                seed: int = 0):
    """(model, params) for a serving replica.  Seeded init: every replica
    of a deployment materializes identical weights without shipping
    params through init args.

    ``config_kw`` are the model's config fields; ``tiny`` (default True:
    the tests' and examples' toy presets) starts from ``<Config>.tiny()``,
    so a configuration at published widths passes ``"tiny": False`` and
    every size.  The leaves are made one by one in the config's
    ``param_dtype`` (``LlamaConfig``; float32 by default and for GPT-2):
    a model served in bfloat16 passes ``"param_dtype": "bfloat16"`` and is
    never held whole in float32.  Returns when the parameters are on
    the device: the ``model.build`` lifecycle span is the whole of it."""
    import jax

    jax_env.ensure_compile_listener()
    with obs.span("model.build", _lifecycle=True,
                  model_kind=model_kind) as sp:
        model, params = _build_model(model_kind, config_kw, seed)
        leaves = jax.tree_util.tree_leaves(jax.block_until_ready(params))
        sp.set(param_count=sum(x.size for x in leaves),
               param_bytes=sum(x.nbytes for x in leaves))
    return model, params


def _build_model(model_kind: str, config_kw: Optional[dict], seed: int):
    import jax
    import jax.numpy as jnp

    config_kw = dict(config_kw or {})
    if model_kind == "gpt2":
        from ray_tpu.models import GPT2, GPT2Config

        model = GPT2(GPT2Config.tiny(**config_kw) if config_kw.pop(
            "tiny", True) else GPT2Config(**config_kw))
    elif model_kind == "llama":
        from ray_tpu.models import Llama, LlamaConfig

        model = Llama(LlamaConfig.tiny(**config_kw) if config_kw.pop(
            "tiny", True) else LlamaConfig(**config_kw))
    elif model_kind == "falcon_h1":
        from ray_tpu.models import FalconH1, FalconH1Config

        model = FalconH1(FalconH1Config.tiny(**config_kw) if config_kw.pop(
            "tiny", True) else FalconH1Config(**config_kw))
    elif model_kind == "nemotron_h":
        from ray_tpu.models import NemotronH, NemotronHConfig

        model = NemotronH(NemotronHConfig.tiny(**config_kw) if config_kw.pop(
            "tiny", True) else NemotronHConfig(**config_kw))
    elif model_kind == "ling_linear":
        # imported here and nowhere else: no other kind's set-up pays for it
        from ray_tpu.models.ling_linear import LingLinear, LingLinearConfig

        model = LingLinear(LingLinearConfig.tiny(**config_kw)
                           if config_kw.pop("tiny", True)
                           else LingLinearConfig(**config_kw))
    elif model_kind == "glm_dsa":
        # imported here and nowhere else, as ``ling_linear``
        from ray_tpu.models.glm_dsa import GlmDsa, GlmDsaConfig

        model = GlmDsa(GlmDsaConfig.tiny(**config_kw)
                       if config_kw.pop("tiny", True)
                       else GlmDsaConfig(**config_kw))
    elif model_kind == "latent_moe":
        # imported here and nowhere else, as ``ling_linear``
        from ray_tpu.models.latent_moe import LatentMoE, LatentMoEConfig

        model = LatentMoE(LatentMoEConfig.tiny(**config_kw)
                          if config_kw.pop("tiny", True)
                          else LatentMoEConfig(**config_kw))
    elif model_kind == "eva_decoder":
        # imported here and nowhere else, as ``ling_linear``
        from ray_tpu.models.eva_decoder import EvaDecoder, EvaDecoderConfig

        model = EvaDecoder(EvaDecoderConfig.tiny(**config_kw)
                           if config_kw.pop("tiny", True)
                           else EvaDecoderConfig(**config_kw))
    else:
        raise ValueError(f"unknown model_kind {model_kind!r}")
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), ids)["params"]
    return model, params


def cache_namespace_for(model_kind: str, config_kw: Optional[dict],
                        seed: int, page_size: int,
                        weight_version: Optional[int] = None) -> str:
    """Stable prefix-cache namespace: everything that changes a page's
    bytes (model family, config, init seed, page geometry — and, since
    hot weight swaps exist, the weight version) must be in the address,
    so deployments sharing an object plane can't poison each other.

    ``weight_version=None`` returns the UNVERSIONED base — the form to
    hand ``LLMEngine(cache_namespace=...)``, which folds its own live
    version in on every ``swap_weights`` (see
    ``prefix_cache.versioned_namespace``).  Pass an explicit version to
    address a specific weight generation from outside the engine
    (tests, external publishers)."""
    kw = sorted((config_kw or {}).items())
    base = f"{model_kind}|{kw!r}|seed{seed}|ps{page_size}"
    if weight_version is None:
        return base
    from ray_tpu.serve.prefix_cache import versioned_namespace

    return versioned_namespace(base, weight_version)


class LLMServer:
    """Serve deployment callable hosting one LLMEngine per replica.

    Use with ``@serve.deployment`` / ``serve.run``; autoscaling sees the
    handle's in-flight count like any deployment, so a saturating client
    scales replicas up through the normal controller loop.  Three entry
    points:

    - ``__call__({"tokens": [...], "max_new_tokens": n, "temperature":
      t, "top_p": p, "seed": s})`` — JSON/HTTP.
    - ``generate_batch(refs, ...)`` — the zero-copy object-plane path
      (prompt refs in via ``get_many``, output refs back via
      ``put_many``); pair with :func:`generate_many` client-side.
    - ``submit_stream``/``next_chunk`` — pull-based token streaming.

    Serving-tier knobs: ``draft_config_kw`` + ``spec_tokens`` enable
    speculative decoding (the draft is built from the same seed, so
    replicas agree); ``prefix_cache=True`` turns on the local prefix
    cache, ``prefix_directory=`` (a ``prefix_cache.create_directory()``
    handle) shares it cluster-wide; ``prefill=`` (a PrefillWorker
    deployment handle) disaggregates prefill.

    ``model_kind`` is what ``build_model`` binds: ``"gpt2"``, ``"llama"``
    (with its layer options, OLMoE's decoder), ``"falcon_h1"`` (a Mamba-2
    mixer beside attention in every block), ``"nemotron_h"`` (a layer is
    a Mamba-2 mixer, an attention or a latent expert layer alone, by
    ``hybrid_override_pattern``; ``experts_held`` / ``expert_offset`` give
    this replica its share of every layer's experts, as one chip of an
    expert-parallel deployment holds it: the router keeps its width and
    the absent experts' part of the result is left out) or
    ``"ling_linear"`` (gated delta-rule layers with one latent-attention
    layer a group, whose latent rows ride the page pool as ONE KV head; a
    leading dense layer; group-routed experts with the same share) or
    ``"glm_dsa"`` (latent attention with a compressed query in every layer,
    whose indexer selects the ``index_topk`` cached rows a query attends
    to: a decode step scores the slot's cached index keys, which ride the V
    row, and gathers the selected latent rows alone; a leading dense layer;
    a sigmoid router's experts with the same share) or ``"latent_moe"``
    (latent attention over the whole cache in every layer under a
    YaRN-scaled rope; the cache is ONE pool of latent rows, read by the
    latent form of the paged kernel; a leading dense layer; a sigmoid
    router's experts with the same share) or ``"eva_decoder"`` (EVA
    attention in every layer: an exact window of rows beside one pooled row
    for every chunk before it; a slot's pages are a ring of window pages
    and summary pages, by the model's ``cache_map``).  For ``"falcon_h1"``,
    ``"nemotron_h"`` and ``"ling_linear"`` the engine holds per-slot
    recurrent state, and for them, ``"glm_dsa"``, ``"latent_moe"`` and
    ``"eva_decoder"`` it refuses the four options above (a cached prefix, a
    draft, a prefix directory, remote prefill).
    """

    def __init__(self, model_kind: str = "gpt2",
                 config_kw: Optional[dict] = None, seed: int = 0,
                 draft_config_kw: Optional[dict] = None,
                 spec_tokens=_DEF, prefix_cache=None,
                 prefix_directory=None, prefill=None,
                 **engine_kw):
        model, params = build_model(model_kind, config_kw, seed)
        draft_model = draft_params = None
        if draft_config_kw is not None:
            draft_model, draft_params = build_model(
                model_kind, draft_config_kw, seed)
        page_size = int(_cfg("serve_page_size",
                             engine_kw.get("page_size", _DEF), 16))
        self.engine = LLMEngine(
            model, params, draft_model=draft_model,
            draft_params=draft_params, spec_tokens=spec_tokens,
            prefix_cache=prefix_cache, prefix_directory=prefix_directory,
            prefill=prefill,
            cache_namespace=cache_namespace_for(model_kind, config_kw,
                                                seed, page_size),
            **engine_kw)

    @staticmethod
    def _sampling_of(request: dict) -> SamplingParams:
        return SamplingParams(
            temperature=float(request.get("temperature", 0.0)),
            top_p=float(request.get("top_p", 1.0)),
            seed=int(request.get("seed", 0)))

    def __call__(self, request: dict) -> dict:
        rid = self.engine.submit(request["tokens"],
                                 int(request.get("max_new_tokens", 16)),
                                 request.get("eos_id"),
                                 sampling=self._sampling_of(request))
        return {"tokens": self.engine.result(rid, timeout=120.0)}

    def generate_batch(self, prompts, max_new_tokens: int = 16,
                       eos_id: Optional[int] = None, as_refs: bool = True,
                       sampling: Optional[list] = None):
        import ray_tpu

        if prompts and isinstance(prompts[0], ray_tpu.ObjectRef):
            prompts = ray_tpu.get_many(list(prompts))
        if sampling is None:
            sampling = [None] * len(prompts)
        rids = [self.engine.submit(p, max_new_tokens, eos_id, sampling=s)
                for p, s in zip(prompts, sampling)]
        outs = [self.engine.result(r, timeout=120.0) for r in rids]
        if not as_refs:
            return outs
        return ray_tpu.put_many([np.asarray(o, np.int32) for o in outs])

    def submit_stream(self, prompt, max_new_tokens: int = 16,
                      eos_id: Optional[int] = None,
                      sampling: Optional[SamplingParams] = None) -> int:
        import ray_tpu

        self.engine.note_reply_call()
        if isinstance(prompt, ray_tpu.ObjectRef):
            prompt = ray_tpu.get(prompt)
        return self.engine.submit(prompt, max_new_tokens, eos_id,
                                  sampling=sampling)

    def next_chunk(self, rid: int, timeout: float = 60.0):
        """Next streamed token chunk, or None when the request retired."""
        self.engine.note_reply_call()
        req = self.engine._requests[rid]
        try:
            chunk = req.chunks.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(f"no chunk for request {rid} in {timeout}s")
        if chunk is None:
            req.consumed = True
        return chunk

    def swap_weights(self, params, version: int,
                     timeout: Optional[float] = 60.0) -> int:
        """Hot-swap this replica's engine weights (``params`` may be the
        broadcast ObjectRef — one learner ``put`` serves every replica;
        replicas resolving the same version concurrently stripe the pull
        across holders and serve each other's landed ranges, see
        docs/PERFORMANCE.md "Multi-source transfers")."""
        return self.engine.swap_weights(params, version, timeout=timeout)

    def generate_rollouts(self, prompts, max_new_tokens: int = 16,
                          eos_id: Optional[int] = None,
                          sampling: Optional[list] = None):
        """Version-stamped rollouts (tokens + behavior logprobs) for the
        RLHF loop; accepts prompt refs like ``generate_batch``."""
        import ray_tpu

        if prompts and isinstance(prompts[0], ray_tpu.ObjectRef):
            prompts = ray_tpu.get_many(list(prompts))
        return self.engine.generate_rollouts(
            prompts, max_new_tokens, eos_id, sampling=sampling)

    def stats(self) -> dict:
        return self.engine.stats()

    def request_stats(self, rid: int) -> dict:
        self.engine.note_reply_call()
        return self.engine.request_stats(rid)

    def autoscale_metric(self) -> float:
        """Engine-load signal for the controller's ``metric_method``
        autoscaling mode: in-flight work per decode slot (1.0 = the
        replica's compiled batch is exactly full)."""
        st = self.engine.stats()
        return (st["active"] + st["pending"]
                + st["prefill_inflight"]) / self.engine.max_slots

    def drain(self):
        """Teardown hook: close the engine (fails in-flight requests with
        a typed error) and any replica-local batchers."""
        self.engine.close()
        from ray_tpu.serve import batching

        batching.close_instance_batchers(self)
        return True


def generate_many(handle, prompts, max_new_tokens: int = 16,
                  eos_id: Optional[int] = None,
                  sampling: Optional[List[SamplingParams]] = None,
                  timeout: float = 120.0) -> List[List[int]]:
    """Client half of the zero-copy request path: one ``put_many`` for
    the prompt batch (one coalesced control-plane notify), one actor call
    carrying refs per affinity group, one ``get_many`` gather of the
    responses.  Prompts are grouped by their prefix affinity key so
    shared-prefix requests land on the replica already holding the
    cached KV pages (see serve/prefix_cache.py)."""
    import ray_tpu
    from ray_tpu.serve.prefix_cache import affinity_key
    from ray_tpu.util import tracing

    # Driver API boundary: the whole request batch (put_many, actor
    # calls, get_many gather, replica decode steps) rides one trace,
    # rooted at this span.
    with tracing.span("serve.generate_many", requests=len(prompts)):
        groups: Dict[str, List[int]] = {}
        for i, p in enumerate(prompts):
            groups.setdefault(affinity_key(p), []).append(i)
        out: List[Optional[List[int]]] = [None] * len(prompts)
        calls = []
        for key, idxs in groups.items():
            refs = ray_tpu.put_many(
                [np.asarray(prompts[i], np.int32) for i in idxs])
            samp = [sampling[i] for i in idxs] if sampling else None
            calls.append((idxs, handle.method("generate_batch").remote(
                refs, max_new_tokens, eos_id, True, samp, _affinity=key)))
        for idxs, call in calls:
            out_refs = ray_tpu.get(call, timeout=timeout)
            vals = ray_tpu.get_many(out_refs)
            for i, v in zip(idxs, vals):
                out[i] = [int(t) for t in v]
        return out
