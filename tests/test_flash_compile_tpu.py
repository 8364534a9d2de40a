"""The flash kernels, and the expert FFN's ``moe_hit`` kernels, compiled for a
described TPU v5e at the widths the train cells, the Llama-family models
and OLMoE's decode step run, without a chip: what Mosaic
refuses (a block that does not fit VMEM, a slice off the tiling) fails here
and costs no chip time.  Nothing runs, so this gives no result and no time.

The topology is described inside a fixture, never at import: only the
worker that is handed this file loads the TPU's library, and every worker
collects the same tests (on-chip-measurement guide, section 2)."""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.attention import (_auto_blocks, _heads_per_block,
                                   _whole_head_fits, flash_attention,
                                   flash_attention_qkv)


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here, or its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An executable compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these compiles out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("shape,dtype,kernels,first", [
    # the train cells: [8, 1024, 16, 64] bf16 on every chip, a pair of
    # heads a column block of the projection's own [B, L, H * D]
    ((8, 1024, 16, 64), jnp.bfloat16, ("flash_fwd", "flash_bwd"),
     "bf16[8,1024,1024]"),
    # ... and as GPT-2's block calls them, q, k and v inside one qkv: the
    # backward's one result is dqkv
    ((8, 1024, 16, 64), "qkv", ("flash_fwd", "flash_bwd"),
     "bf16[8,1024,3072]"),
    # Llama-family heads, a head a block: no mask
    ((2, 1024, 16, 128), jnp.bfloat16, ("flash_fwd", "flash_bwd"),
     "bf16[2,1024,2048]"),
    # ... at the longest length the fused backward holds
    ((1, 2048, 8, 128), jnp.bfloat16, ("flash_fwd", "flash_bwd"),
     "bf16[1,2048,1024]"),
    # a pair of heads of 64 at 2,048 is 20 tile bodies, past what the
    # kernels unroll: head-major, a head a grid step
    ((4, 2048, 16, 64), jnp.bfloat16, ("flash_fwd", "flash_bwd"),
     "bf16[64,2048,64]"),
    # past its residency: the two-kernel form, K and V whole beside a tile
    ((1, 4096, 12, 64), jnp.bfloat16, ("flash_fwd", "flash_dq",
                                       "flash_dkv"), "bf16[12,4096,64]"),
    ((1, 8192, 4, 128), jnp.bfloat16, ("flash_fwd", "flash_dq",
                                       "flash_dkv"), "bf16[4,8192,128]"),
    ((2, 1024, 4, 64), jnp.float32, ("flash_fwd", "flash_bwd"),
     "f32[2,1024,256]"),
])
def test_flash_kernels_compile_for_v5e(one_chip, shape, dtype, kernels,
                                       first):
    """``first``: the first result of the backward's (last) kernel, which
    is how a device trace names it and what says which layout ran."""
    b, length, h, d = shape
    if dtype == "qkv":
        dtype = jnp.bfloat16
        args = (jax.ShapeDtypeStruct((b, length, 3 * h * d), dtype,
                                     sharding=one_chip),)
        attend = lambda qkv: flash_attention_qkv(qkv, h, causal=True)  # noqa: E731
    else:
        args = (jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip),) * 3
        attend = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
    grad = jax.jit(jax.grad(
        lambda *a: jnp.sum(attend(*a).astype(jnp.float32)),
        argnums=tuple(range(len(args)))))
    text = grad.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    for name in ("flash_fwd", "flash_bwd", "flash_dq", "flash_dkv"):
        assert (name in text) == (name in kernels), name
    calls = [line.split(" = ")[1].lstrip("(") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls[-1].startswith(first), calls
    itemsize, blocks = jnp.dtype(dtype).itemsize, _auto_blocks(
        length, length, d, True)
    whole = _whole_head_fits(length, length, d, itemsize, *blocks, True)
    assert whole == ("flash_bwd" in kernels)
    lanes = _heads_per_block(length, length, h, d, itemsize, *blocks, True)
    assert bool(lanes) == (f"[{b},{length}," in first)


@pytest.mark.timeout(120)
@pytest.mark.parametrize("shape,fused,bodies", [
    ((8, 1024, 16, 64), True, 6),    # the one-chip train cell: qkv, dqkv
    ((8, 1024, 16, 64), False, 6),   # the four-chip one: three operands
    ((2, 1536, 4, 64), False, 12),   # the most tile bodies a block of two
                                     # heads has where it fits VMEM
    ((1, 2048, 8, 128), False, 10),  # the longest: four masked tiles a head
])
def test_cut_tiles_compile_for_v5e(one_chip, shape, fused, bodies):
    """The whole-head kernels with their masked tiles cut into chunks of 128
    columns (PR 54): the slices and joins the cut makes lie on the tiling,
    the kernels fit VMEM, and the tile bodies ``_whole_head_fits`` counts
    (by tiles visited: a chunk is part of its tile's body) compile."""
    from ray_tpu.ops.attention import (_MAX_UNROLLED_TILES,
                                       causal_tile_schedule)

    b, length, h, d = shape
    blocks = _auto_blocks(length, length, d, True)
    sched = causal_tile_schedule(length, length, *blocks)
    assert sched["chunk"] == 128
    assert sched["multiplied_share"] < sched["visited_share"]
    heads = _heads_per_block(length, length, h, d, 2, *blocks, True)
    assert heads and sched["visited"] * heads == bodies <= _MAX_UNROLLED_TILES
    if fused:
        args = (jax.ShapeDtypeStruct((b, length, 3 * h * d), jnp.bfloat16,
                                     sharding=one_chip),)
        attend = lambda qkv: flash_attention_qkv(qkv, h, causal=True)  # noqa: E731
    else:
        args = (jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                     sharding=one_chip),) * 3
        attend = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(attend(*a).astype(jnp.float32)),
        argnums=tuple(range(len(args))))).lower(*args).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd" in text


@pytest.mark.timeout(120)
@pytest.mark.parametrize("rows,experts,d,f", [
    (16, 64, 2048, 1024),   # OLMoE's decode step: 16 slots, one token each
    (512, 64, 2048, 1024),  # its 512-row prefill bucket: the most rows
    (1, 8, 1024, 3584),     # one row; a width that 1024 does not divide
])
def test_moe_hit_compiles_for_v5e(one_chip, monkeypatch, rows, experts, d, f):
    """The kernel as ``experts_dropless`` calls it for few rows, with the
    list made in the same program.  (``jax.default_backend`` is this
    process's, the CPU: said to be the chip's, so the kernel is compiled
    and not interpreted.)"""
    from ray_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    assert rows <= moe.DENSE_MAX_ROWS
    text = jax.jit(moe.experts_dropless).lower(
        shape(rows, d), shape(rows, 8, dtype=jnp.float32),
        shape(rows, 8, dtype=jnp.int32), shape(experts, d, f),
        shape(experts, d, f), shape(experts, f, d),
        shape(rows, dtype=jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" in text and "moe_hit" in text


@pytest.mark.timeout(120)
@pytest.mark.parametrize("rows", [
    48,    # Nemotron-3-Super's decode step: 48 slots, one token each
    512,   # its 512-row prefill bucket: the most rows the kernel takes
])
def test_moe_hit_relu2_compiles_for_v5e(one_chip, monkeypatch, rows):
    """The two-matrix kernel as ``experts_held_relu2`` calls it for few
    rows at the published widths (latent 1024, expert 2688 in tiles of 896,
    top-22 of 512 of which 128 are held)."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    assert rows <= moe.DENSE_MAX_ROWS
    assert moe._tile_of(2688, moe.HIT_TILE_BYTES // (1024 * 2)) == 896
    text = jax.jit(moe.experts_held_relu2,
                   static_argnames="expert_offset").lower(
        shape(rows, 1024), shape(rows, 22, dtype=jnp.float32),
        shape(rows, 22, dtype=jnp.int32), shape(128, 1024, 2688),
        shape(128, 2688, 1024), expert_offset=128,
        active=shape(rows, dtype=jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" in text and "moe_hit_relu2" in text


@pytest.mark.timeout(120)
@pytest.mark.parametrize("rows", [
    48,    # Ling-3.0-flash's decode step: 48 slots, one token each
    512,   # its 512-row prefill bucket: the most rows the kernel takes
])
def test_moe_hit_compiles_for_v5e_as_a_share_of_swiglu_experts(
        one_chip, monkeypatch, rows):
    """The three-matrix kernel as ``experts_held_swiglu`` calls it for few
    rows at the published widths (hidden 2560, expert 768 in tiles of 384,
    top-8 of 512 of which 128 are held)."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    assert rows <= moe.DENSE_MAX_ROWS
    assert moe._tile_of(768, moe.HIT_TILE_BYTES // (2560 * 2)) == 384
    text = jax.jit(moe.experts_held_swiglu,
                   static_argnames="expert_offset").lower(
        shape(rows, 2560), shape(rows, 8, dtype=jnp.float32),
        shape(rows, 8, dtype=jnp.int32), shape(128, 2560, 768),
        shape(128, 2560, 768), shape(128, 768, 2560), expert_offset=0,
        active=shape(rows, dtype=jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" in text and "moe_hit" in text


@pytest.mark.timeout(120)
def test_paged_attention_compiles_for_v5e_over_one_latent_head(
        one_chip, monkeypatch):
    """The paged kernel as an absorbed latent-attention layer calls it: 48
    slots, 32 query heads on ONE KV head of 576 columns in a pool 640 wide,
    ``sm_scale`` given; its first result is ``f32[48,32,640]``, which the
    benchmark's ``latent_paged_attn_roofline`` tells it by."""
    from ray_tpu.ops.paged_attention import paged_attention, pool_width

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    slots, pages = 48, 512
    assert pool_width(1, 576) == 640
    pool = shape(1, slots * pages + 1, 16, 640)
    text = jax.jit(lambda q, k, v, kp, vp, table, lengths: paged_attention(
        q, k, v, kp, vp, 0, table, lengths, sm_scale=192 ** -0.5)).lower(
        shape(slots, 1, 32, 576), shape(slots, 1, 1, 576),
        shape(slots, 1, 1, 576), pool, pool,
        shape(slots, pages, dtype=jnp.int32),
        shape(slots, dtype=jnp.int32)).compile().as_text()
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "paged_attn" in call
    assert call.split(" = ")[1].startswith("(f32[48,32,640]")


@pytest.mark.timeout(120)
def test_latent_paged_attention_compiles_for_v5e_one_row_a_token(
        one_chip, monkeypatch):
    """The latent form of the paged kernel as ``models/latent_moe.py``'s
    decode step calls it: 32 slots of up to 16,384 rows, 64 query heads on
    ONE cached row of 576 columns in a pool 640 wide of eight layers, no V
    pool; its first result is ``f32[32,64,512]`` (the accumulator is
    ``kv_lora_rank`` wide), which the benchmark's
    ``mla_paged_attn_roofline`` tells it by, and nothing of the pool's size
    is written."""
    from ray_tpu.ops import paged_attention as pa
    from tools.step_fusions import entry_operations

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    slots, pages = 32, 1024
    pool = shape(8, slots * pages + 1, 16, pa.pool_width(1, 576))
    compiled = jax.jit(lambda q, row, kp, table, lengths:
                       pa.latent_paged_attention(
                           q, row, None, kp, None, 5, table, lengths,
                           sm_scale=192 ** -0.5 * 1.3689 ** 2,
                           rank=512)).lower(
        shape(slots, 1, 64, 576), shape(slots, 1, 1, 576), pool,
        shape(slots, pages, dtype=jnp.int32),
        shape(slots, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "latent_paged_attn" in call
    assert call.split(" = ")[1].startswith("(f32[32,64,512]")
    big = [o["name"] for o in entry_operations(text)
           if any(s.startswith("bf16[8,32769") for s in o["shapes"])
           and o["op"] not in ("parameter", "bitcast")]
    assert not big, big
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.timeout(180)
def test_eva_decode_kernels_compile_for_v5e_at_32_kv_heads_of_128(
        one_chip, monkeypatch):
    """What a decode step of ``models/eva_decoder.py`` asks of the chip at
    the published widths, 16 slots of 256 pages: the paged kernel over pages
    4,096 columns wide (32 KV heads of 128: its two step buffers are 4 MiB,
    under the compiler's default of scoped VMEM, so it asks for no limit of
    its own; its first result is ``f32[16,32,4096]``, which the benchmark's
    ``eva_paged_attn_roofline`` tells it by), and ``eva_page_gather``, which
    hands the step the pages of the chunks its tokens close and writes
    nothing of the pool's size (indexing the pool made the compiler lay the
    whole of it out anew: 4 GiB of scratch)."""
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops.eva import gather_pages
    from tools.step_fusions import entry_operations

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    slots, pages = 16, 256
    assert pa.pool_width(32, 128) == 4096
    pool = shape(8, slots * pages + 1, 16, 4096)
    text = jax.jit(lambda q, k, v, kp, vp, table, lengths: pa.paged_attention(
        q, k, v, kp, vp, 5, table, lengths)).lower(
        shape(slots, 1, 32, 128), shape(slots, 1, 32, 128),
        shape(slots, 1, 32, 128), pool, pool,
        shape(slots, pages, dtype=jnp.int32),
        shape(slots, dtype=jnp.int32)).compile().as_text()
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "paged_attn" in call
    assert call.split(" = ")[1].startswith("(f32[16,32,4096]")
    found = re.search(
        r'"scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"', call)
    assert not found or int(found.group(1)) <= 16 * 2 ** 20

    compiled = jax.jit(gather_pages).lower(
        pool, shape(slots, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "eva_page_gather" in call
    assert call.split(" = ")[1].startswith("bf16[8,16,16,4096]")
    big = [o["name"] for o in entry_operations(text)
           if any(s.startswith("bf16[8,4097") for s in o["shapes"])
           and o["op"] not in ("parameter", "bitcast")]
    assert not big, big
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.timeout(180)
def test_flash_forward_compiles_for_v5e_at_16k_rows_of_192(one_chip):
    """A block of 16 heads of 192 over 16,384 rows, as the latent-attention
    prefill of the 16,384 bucket calls the forward kernel: K and V whole
    beside the q tiles are 32 MiB, past the compiler's default of 16 MiB of
    scoped VMEM, so the call asks for its own limit (``_fwd_call``), as the
    8,192 bucket's does (16 MiB); at 4,096 rows, the longest such call
    before PR 56 (Ling's), it asks for nothing and is compiled as it
    was."""
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.bfloat16, sharding=one_chip)
    for rows, asks in ((16384, True), (8192, True), (4096, False)):
        text = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, sm_scale=0.1)).lower(
            *[shape(1, rows, 16, 192)] * 3).compile().as_text()
        call = next(line for line in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in line)
        assert "flash_fwd" in call
        assert call.split(" = ")[1].startswith(f"bf16[16,{rows},192]")
        found = re.search(
            r'"scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"', call)
        scoped = int(found.group(1)) if found else 0
        assert (scoped > 16 * 2 ** 20) == asks, scoped


@pytest.mark.timeout(120)
@pytest.mark.parametrize("heads,head,state,groups", [
    (32, 128, 256, 2),   # Falcon-H1-34B's mixer: 4 MiB a slot a layer
    (128, 64, 128, 8),   # Nemotron-3-Super's: likewise
])
def test_ssm_step_compiles_for_v5e_and_updates_the_pool_in_place(
        one_chip, monkeypatch, heads, head, state, groups):
    """The decode step's state pass over 48 slots at the published widths,
    with the list made in the same program: the kernel's first result is
    the pool in its own shape (the benchmark's state rooflines tell the
    pass by it), and the donated pool is the result's buffer: no second
    pool, no scratch of its size."""
    from ray_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    slots, f32 = 48, jnp.float32
    pool = shape(slots, heads, head, state, dtype=f32)
    compiled = jax.jit(
        lambda pool, active, *rest: ssm.ssm_step(
            pool, *ssm.live_slots(active), *rest),
        donate_argnums=0).lower(
        pool, shape(slots, dtype=jnp.bool_), shape(slots, heads, head),
        shape(slots, heads, dtype=f32), shape(heads, dtype=f32),
        shape(slots, groups, state), shape(slots, groups, state)).compile()
    text = compiled.as_text()
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "ssm_step" in call
    assert call.split(" = ")[1].startswith(
        f"(f32[{slots},{heads},{head},{state}]")
    memory = compiled.memory_analysis()
    pool_bytes = slots * heads * head * state * 4
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 48


@pytest.mark.timeout(120)
@pytest.mark.parametrize("wants_float32", [False, True], ids=[
    "next_token_cross_entropy",
    "log_softmax_left_to_autodiff",  # the test can see one
])
def test_loss_head_compiles_for_v5e_without_float32_logits(
        one_chip, wants_float32):
    """GPT-2's tied head and loss at the train cells' vocabulary (50,257: no
    multiple of 128) and widths, a few rows: ``ops/losses.py``'s backward
    pass leaves no float32 array of the logits' shape in the program, forward
    or backward, where autodiff of ``log_softmax`` writes one."""
    from ray_tpu.ops.losses import next_token_cross_entropy
    from tools.step_fusions import entry_operations

    def left_to_autodiff(logits, ids):
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, ids[:, 1:, None], axis=-1))

    fn = left_to_autodiff if wants_float32 else next_token_cross_entropy
    b, l, d, v = 2, 256, 1024, 50257

    def head_loss(x, wte, ids):
        logits = jnp.einsum("bld,vd->blv", x.astype(jnp.bfloat16),
                            wte.astype(jnp.bfloat16)).astype(jnp.float32)
        return fn(logits, ids)

    shape = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    # results of the program's own instructions: what is written to memory,
    # not what a fusion holds in registers
    written = entry_operations(
        jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1))).lower(
            shape(b, l, d), shape(v, d),
            shape(b, l, dtype=jnp.int32)).compile().as_text())
    found = [(o["name"], o["shapes"]) for o in written
             if {f"f32[{b},{l},{v}]", f"f32[{b},{l - 1},{v}]"}
             & set(o["shapes"])]
    assert bool(found) == wants_float32, found
    if not wants_float32:
        # ... and one bf16 array of that shape, the logits: the gradient is
        # made inside the two backward matmuls, as their operand
        kept = [o["name"] for o in written
                if o["op"] == "fusion" and f"bf16[{b},{l},{v}]" in o["shapes"]]
        assert len(kept) == 1, kept


@pytest.mark.timeout(180)
@pytest.mark.parametrize("keep", [False, True])
def test_sparse_decode_compiles_for_v5e_and_copies_no_pool(one_chip,
                                                           monkeypatch, keep):
    """One layer's decode attention under a learned selection at GLM-5's
    widths (16 slots of 16,384 rows, 64 heads on ONE latent head of 576 in
    a pool 640 wide of five layers, an indexer of 32 heads of 128 keeping
    2,048 rows): the index kernel ``dsa_index`` reads the keys where they
    lie (its result ``f32[16,1,16384]``, which the benchmark's
    ``dsa_indexer_roofline`` tells it by), the selected rows are one gather
    by row (``[16,2048,640]``, ``sparse_paged_attn_roofline``'s), and no
    operation writes anything of the pool's size (indexed as [layers,
    pages, page, width] for the keys' columns, the compiler re-laid the
    whole V pool out: 12.5 GB).  ``keep``: the form a recording engine
    runs, whose sort carries each row's position too."""
    from ray_tpu.ops.dsa import sparse_paged_attention
    from tools.step_fusions import entry_operations

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    slots, pages = 16, 1024
    pool = shape(5, slots * pages + 1, 16, 640)

    def attend(q, k, v, kp, vp, table, lengths, qi, wi, ki):
        return sparse_paged_attention(
            q, k, v, k_pool=kp, v_pool=vp, layer=3, table=table,
            lengths=lengths, index=(qi, wi, ki), topk=2048,
            sm_scale=256 ** -0.5, rank=512, keep=keep)

    compiled = jax.jit(attend).lower(
        shape(slots, 1, 64, 576), shape(slots, 1, 1, 576),
        shape(slots, 1, 1, 576), pool, pool,
        shape(slots, pages, dtype=jnp.int32), shape(slots, dtype=jnp.int32),
        shape(slots, 1, 32, 128), shape(slots, 1, 32, dtype=jnp.float32),
        shape(slots, 1, 128)).compile()
    text = compiled.as_text()
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "dsa_index" in call
    assert call.split(" = ")[1].startswith("f32[16,1,16384]")
    written = entry_operations(text)
    assert any("bf16[16,2048,640]" in o["shapes"] for o in written)
    big = [o["name"] for o in written
           if any(s.startswith(("bf16[5,16385", "bf16[1310800"))
                  for s in o["shapes"])
           and o["op"] not in ("parameter", "bitcast")]
    assert not big, big
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.timeout(420)
def test_anakin_ppo_step_keeps_its_frames_bytes_for_v5e(one_chip):
    """``ppo_atari84_anakin``'s whole step at the cell's sizes (2,048 envs
    x 64 steps, minibatches of 8,192), built as the benchmark's driver
    builds it (``tools/step_fusions.py``; ~35 s alone).  The trajectory
    holds packed frames as word tiles, four bytes a word, a frame 64 rows of
    128 words, written a rollout step at a time where they lie (the
    kernel's result is the buffer: no copy of the trajectory anywhere in
    the step, nor a pass to clear it) by ``fold_tiles``, which takes the
    env's raw frames AS THE LOOP CARRIES THEM (channel-major, the batch in
    the lanes: a bitcast, not a copy) and pads and folds on the way, so a
    rollout step moves its frames once: until PR 60 ``pack_frames`` in four
    passes of the compiler's (``copy u8[2048,84,336]``, ``pad
    u8[2048,88,352]``, two more copies) and ``tile_columns`` behind them.
    Nothing frame-sized is ever written in two bytes or four a value (PR
    55's parent wrote ``bf16[8192,84,84,4]`` twice a minibatch), and a
    minibatch's frames are moved ONCE, as bytes: the kernel ``gather_rows``
    writes them batch-minor, and both of ``Conv_0``'s fusions read that
    through a bitcast (until PR 58 a gather and a transposition,
    ``fusion`` + ``copy u8[8192,242,128]``).  Nothing chooses at run time,
    so this is the mechanism's witness."""
    from tools.step_fusions import compile_step, entry_operations

    text = compile_step("ppo_atari84_anakin",
                        sorted(one_chip.device_set, key=lambda d: d.id)
                        ).as_text()
    assert "bf16[8192,84,84,4]" not in text
    assert "u32[131072,64,128]" in text        # the trajectory, word tiles
    assert not re.search(r"= u32\[(131072,64|8388608),128\]\S* (copy|fusion|"
                         r"broadcast|dynamic-update-slice)\(", text)
    assert "u8[64,2048,242,128]" not in text   # ... and no bytes beside it
    assert "u8[64,2048,84,84,4]" not in text   # ... nor raw frames

    def size(shape):
        kind, dims = re.fullmatch(r"(\w+)\[([\d,]*)\]", shape).groups()
        return kind, math.prod(int(d) for d in dims.split(",") if d)

    moved, stepped, kernels = [], [], []
    for o in entry_operations(text):
        if o["op"] == "custom-call" and "tpu_custom_call" in o["key"]:
            kernels.append((o["times"], o["name"].rsplit(".", 1)[0],
                            *o["shapes"]))
        if o["op"] in ("get-tuple-element", "bitcast", "parameter", "tuple",
                       "while"):
            continue
        kind, values = size(o["shapes"][0])
        # 2 epochs x 16 minibatches: the inner loop's body
        if o["times"] == 32 and values >= 8192 * 22 * 22 * 64:
            moved.append((o["op"], kind))
        # 64 rollout steps: whatever writes 2,048 frames of bytes
        if o["times"] == 64 and kind == "u8" and values >= 2048 * 84 * 84 * 4:
            stepped.append(o["key"])
    assert moved == [("custom-call", "u8")]
    # the env shifts its frame stack, and one kernel takes it from there
    assert stepped == ["broadcast_select_fusion u8[2048,84,84,4]"]
    # the buffer, a rollout step's fold_tiles, a minibatch's gather_rows
    assert sorted(kernels) == [
        (1, "empty_tiles", "u32[131072,64,128]"),
        (32, "gather_rows", "u8[30976,8192]"),
        (64, "fold_tiles", "u32[8388608,128]", "u8[30976,2048]")]
    # ... which reads the loop's own frames: no relayout in front of it
    call = next(line for line in text.splitlines()
                if " custom-call(" in line and "fold_tiles" in line)
    frames = re.search(r"custom-call\(%[\w.\-]+, %([\w.\-]+),", call)[1]
    made = next(line for line in text.splitlines()
                if line.lstrip().startswith(f"%{frames} = "))
    assert " bitcast(" in made and "u8[4,84,84,2048]" in made


@pytest.mark.timeout(120)
@pytest.mark.parametrize("n,frame,vmem_mb", [
    (2048, (84, 84), 25),     # the PPO cell's rollout step
    (256, (210, 160), 105),   # a whole Atari screen: most of a core's VMEM
], ids=["84x84", "210x160"])
def test_fold_tiles_compiles_for_v5e(one_chip, n, frame, vmem_mb):
    """``ops.gather_rows.fold_tiles`` at real sizes, in place: Mosaic takes
    a row of 84 bytes (not whole 32-row tiles) as words and the fold's
    strided stores of 21 rows, and the block fits the VMEM asked for."""
    from ray_tpu.models.nature_cnn import _pads
    from ray_tpu.ops import gather_rows as rows_op

    pads = tuple(map(tuple, _pads(*frame)))
    x = jax.ShapeDtypeStruct((n, *frame, 4), jnp.uint8, sharding=one_chip)
    assert rows_op.folds_frames(x, pads)
    fold = rows_op._Fold.of((*frame, 4), pads)
    assert fold.vmem <= vmem_mb << 20
    into = jax.ShapeDtypeStruct(
        (2 * n, rows_op._tile_rows(fold.words), 128), jnp.uint32,
        sharding=one_chip)
    at = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda x, into, at: rows_op._fold_tiles(x, into, at, pads=pads,
                                                interpret=False),
        donate_argnums=(1,)).lower(x, into, at).compile()
    call = next(line for line in compiled.as_text().splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "fold_tiles" in call and "output_to_operand_aliasing" in call
    # the buffer is the result: nothing of its size beside it
    assert compiled.memory_analysis().temp_size_in_bytes \
        < n * (math.prod(frame) * 4 + fold.words * 4) * 1.3
