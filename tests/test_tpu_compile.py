"""The main-path Pallas kernels, compiled by the installed TPU compiler for
a DESCRIBED v5e (no chip attached) at Llama-3-8B widths: 32 heads, 8 KV
heads, head 128, hidden 4096, MLP 14336, bf16, the serving defaults of
``block_size`` 16 and ``chunk_tokens`` 256.

Interpret mode cannot see what these catch: a block shape the TPU cannot
tile, a reshape Mosaic has no layout for, more scoped VMEM than a kernel
may use.  Each of those stopped a kernel here before it ever reached a
chip (PR 22).  Nothing runs, so results are the parity tests' business
(test_kernels, test_fused_serving, test_quantized_serving).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file.  All such tests stay in this one file for the same reason.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, KVH, D, HIDDEN, MLP = 32, 8, 128, 4096, 14336
SEQ, CHUNK, BATCH = 2048, 256, 8
NUM_BLOCKS, BLOCK, PAGES = 1024, 16, 128
bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


def _kernel(name):
    # the package re-exports functions under some of the modules' names
    return importlib.import_module(f"paddle_tpu.kernels.{name}")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """``compile_for_chip(fn, (shape, dtype), ...)`` -> compiled HLO text
    for one described chip.  The persistent compile cache is off around
    these compiles: an entry written for a described chip cannot be read
    back without one, and only warns."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *avals):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in avals]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sum_grads(fn, n):
    """Gradients of ``sum(fn(*args))`` in all ``n`` arguments."""
    return jax.grad(lambda *a: fn(*a).astype(f32).sum(),
                    argnums=tuple(range(n)))


def test_flash_attention_forward(compile_for_chip):
    fa = _kernel("flash_attention")
    q = ((1, H, SEQ, D), bf16)
    text = compile_for_chip(
        lambda q, k, v: fa.flash_attention_bhtd(q, k, v, causal=True,
                                                interpret=False), q, q, q)
    assert text.count("tpu_custom_call") >= 1
    assert "flash_attention_fwd" in text


def test_flash_attention_backward_with_gqa(compile_for_chip):
    fa = _kernel("flash_attention")
    kv = ((1, SEQ, KVH, D), bf16)
    text = compile_for_chip(
        _sum_grads(lambda q, k, v: fa.flash_attention_bthd(
            q, k, v, causal=True, interpret=False), 3),
        ((1, SEQ, H, D), bf16), kv, kv)
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert name in text
    assert "tpu_custom_call" in text


def test_rms_norm_at_hidden_4096(compile_for_chip):
    # 512-row blocks at this width were 16.01M of 16.00M scoped VMEM
    rn = _kernel("rms_norm")
    text = compile_for_chip(
        lambda x, w: rn.rms_norm(x, w, 1e-5, interpret=False),
        ((SEQ, HIDDEN), bf16), ((HIDDEN,), bf16))
    assert "tpu_custom_call" in text and "rms_norm" in text


@pytest.mark.parametrize("heads", [H, KVH])
def test_fused_rope_forward_and_backward(compile_for_chip, heads):
    # the backward is the same kernel on the cotangent
    rope = _kernel("rope")
    text = compile_for_chip(
        lambda x, c, s: _sum_grads(
            lambda x_: rope.fused_rope(x_, c, s, interpret=False), 1)(x),
        ((1, SEQ, heads, D), bf16), ((SEQ, D // 2), bf16),
        ((SEQ, D // 2), bf16))
    assert "tpu_custom_call" in text and "fused_rope" in text
    # Alone the kernel compiled at 14.3M of the 16M scoped VMEM with
    # 256-row tiles of 32 heads; inside the whole train step XLA fuses
    # producers into its operands and the same call needed 21.9M.  Half
    # the budget is the kernel's to use.
    used = [int(n) for line in text.splitlines()
            if "tpu_custom_call" in line and "fused_rope" in line
            for n in re.findall(r'"used_scoped_memory_configs":\[\{'
                                r'"memory_space":"1","offset":"0",'
                                r'"size":"(\d+)"', line)]
    assert used and max(used) <= 8 << 20


def _one_bf16_product(text, rows, k, width):
    """The folded norm's projection as XLA lowers it since PR 37 took the
    Pallas kernel out (PERF.md section 6): no kernel call, one matmul
    accumulated in float32, and the weight never widened on its way in."""
    assert "tpu_custom_call" not in text
    assert f"f32[{rows},{width}]" in text and "convolution(" in text
    assert f"f32[{k},{width}]" not in text


# the MLP's gate behind a folded norm, then that model's projections
# into q (4096) and into k and v (1024), at a decode bucket's 32 rows
# (8 in the first case) and a chunk's 256
@pytest.mark.parametrize("rows,width,act", [
    (BATCH, MLP, "silu"), (CHUNK, MLP, "silu"),
    (32, HIDDEN, "none"), (CHUNK, HIDDEN, "none"),
    (32, KVH * D, "none"), (CHUNK, KVH * D, "none")])
def test_fused_norm_linear(compile_for_chip, rows, width, act):
    fnl = _kernel("fused_norm_linear")
    text = compile_for_chip(
        lambda x, rs, nw, w: fnl.fused_norm_linear(
            x, rs, nw, w, activation=act),
        ((rows, HIDDEN), bf16), ((rows, 1), f32), ((HIDDEN,), bf16),
        ((HIDDEN, width), bf16))
    _one_bf16_product(text, rows, HIDDEN, width)


_POOLS = [(None, bf16), ("int8", i8), ("fp8", i8)]
_POOL_IDS = ["bf16", "int8", "fp8"]


def _pool_avals(pool_dtype, kv_dtype, num_blocks=NUM_BLOCKS):
    pool = ((num_blocks, BLOCK, KVH, D), pool_dtype)
    scales = [((num_blocks, BLOCK), f32)] * 2 if kv_dtype else []
    return [pool, pool], scales


# the second case is the benchmark's serving cells: 32 slots, a table of
# 256 pages, 2,048 blocks
@pytest.mark.parametrize("batch,pages,num_blocks",
                         [(BATCH, PAGES, NUM_BLOCKS), (32, 256, 2048)],
                         ids=["batch8", "served32"])
@pytest.mark.parametrize("kv_dtype,pool_dtype", _POOLS, ids=_POOL_IDS)
def test_fused_paged_decode(compile_for_chip, kv_dtype, pool_dtype, batch,
                            pages, num_blocks):
    pa = _kernel("paged_attention")
    pools, scales = _pool_avals(pool_dtype, kv_dtype, num_blocks)
    rope_table = ((pages * BLOCK, D // 2), bf16)

    def decode(q, k_new, v_new, kp, vp, table, pos, cos, sin, *sc):
        ks, vs = sc if sc else (None, None)
        return pa.fused_paged_decode(
            q, k_new, v_new, kp, vp, table, pos, cos, sin,
            use_pallas=True, interpret=False, k_scale=ks, v_scale=vs,
            kv_cache_dtype=kv_dtype)

    text = compile_for_chip(
        decode, ((batch, 1, H, D), bf16), ((batch, 1, KVH, D), bf16),
        ((batch, 1, KVH, D), bf16), *pools, ((batch, pages), i32),
        ((batch,), i32), rope_table, rope_table, *scales)
    assert "tpu_custom_call" in text and "fused_paged_decode" in text


# the later cases are the benchmark's serving cells: a table of 256
# pages; Mistral's 8 KV heads over 2,048 blocks, SDAR's 4 over 4,096
# under the block-causal mask
@pytest.mark.parametrize("kvh,pages,num_blocks,mask_block",
                         [(KVH, PAGES, NUM_BLOCKS, 1), (KVH, 256, 2048, 1),
                          (4, 256, 4096, 4)],
                         ids=["table128", "mistral-served", "sdar-served"])
@pytest.mark.parametrize("kv_dtype,pool_dtype", _POOLS, ids=_POOL_IDS)
def test_fused_chunked_attention(compile_for_chip, kv_dtype, pool_dtype,
                                 kvh, pages, num_blocks, mask_block):
    cp = _kernel("chunked_prefill")
    pool = ((num_blocks, BLOCK, kvh, D), pool_dtype)
    scales = [((num_blocks, BLOCK), f32)] * 2 if kv_dtype else []

    def chunk(q, kp, vp, table, pos, *sc):
        ks, vs = sc if sc else (None, None)
        return cp.fused_chunked_attention(
            q, kp, vp, table, pos, use_pallas=True, interpret=False,
            k_scale=ks, v_scale=vs, kv_cache_dtype=kv_dtype,
            mask_block=mask_block)

    text = compile_for_chip(
        chunk, ((1, CHUNK, H, D), bf16), pool, pool, ((1, pages), i32),
        ((1,), i32), *scales)
    assert "tpu_custom_call" in text and "fused_chunked_prefill" in text
    # the walk's buffers, q, the accumulator and a score tile together:
    # within the 16 MiB a kernel may scope, with room for what XLA fuses
    # into its operands inside a whole step program
    used = [int(n) for line in text.splitlines()
            if "tpu_custom_call" in line and "fused_chunked_prefill" in line
            for n in re.findall(r'"used_scoped_memory_configs":\[\{'
                                r'"memory_space":"1","offset":"0",'
                                r'"size":"(\d+)"', line)]
    assert used and max(used) <= 14 << 20


# ---- a block-diffusion model's kernels at SDAR-30B-A3B widths: 32 heads,
# 4 KV heads, head 128, hidden 2048, 128 experts of width 768, a block of 4
# positions a slot over 32 slots, a pool of 4,096 blocks and a 256-page table
SDAR_KVH, SDAR_HIDDEN, EXPERTS, EXPERT_WIDTH, TOP_K = 4, 2048, 128, 768, 8
SDAR_POOL = ((4096, BLOCK, SDAR_KVH, D), bf16)


@pytest.mark.parametrize("tokens", [32 * 4, CHUNK], ids=["block", "chunk"])
def test_grouped_experts(compile_for_chip, tokens):
    me = _kernel("moe_experts")

    def experts(x, router, wg, wu, wd):
        chosen, gates = me.route_topk(x, router, TOP_K)
        out, stats = me.grouped_experts(x, chosen, gates, wg, wu, wd,
                                        use_pallas=True, interpret=False)
        return out, stats.as_vector()

    w = ((EXPERTS, EXPERT_WIDTH, SDAR_HIDDEN), bf16)
    text = compile_for_chip(experts, ((tokens, SDAR_HIDDEN), bf16),
                            ((SDAR_HIDDEN, EXPERTS), bf16), w, w, w)
    assert "tpu_custom_call" in text and "moe_grouped_experts" in text
    # no loop around the kernel: a trace's rows then add up
    assert " while(" not in text


def test_block_causal_chunked_attention(compile_for_chip):
    cp = _kernel("chunked_prefill")
    text = compile_for_chip(
        lambda q, kp, vp, table, pos: cp.fused_chunked_attention(
            q, kp, vp, table, pos, use_pallas=True, interpret=False,
            mask_block=4),
        ((1, CHUNK, H, D), bf16), SDAR_POOL, SDAR_POOL, ((1, 256), i32),
        ((1,), i32))
    assert "tpu_custom_call" in text and "fused_chunked_prefill" in text


def test_context_partials_of_a_block_of_positions(compile_for_chip):
    pa = _kernel("paged_attention")
    rows = H // SDAR_KVH * 4            # the GQA group times the block
    text = compile_for_chip(
        lambda q, kp, vp, table, last: pa.paged_context_partials(
            q, kp, vp, table, last, use_pallas=True, interpret=False),
        ((32, SDAR_KVH, rows, D), bf16), SDAR_POOL, SDAR_POOL,
        ((32, 256), i32), ((32,), i32))
    assert "tpu_custom_call" in text and "fused_paged_decode" in text


# ---- a window layer's kernels at the widths of the model with window and
# full layers that the benchmark serves: 32 heads, 4 KV heads, head 128,
# a window of 2,048 keys, 16 slots over a 1,024-page table (16 k tokens:
# 16 x 1,024 table entries ride in as ONE scalar-prefetch operand), the
# window group's 2,321 blocks and the full group's 16,384
WINDOW, WINDOW_SLOTS, WINDOW_PAGES = 2048, 16, 1024
WINDOW_POOLS = {"window": (2321, WINDOW), "full": (16384, None)}


@pytest.mark.parametrize("group", sorted(WINDOW_POOLS))
def test_fused_paged_decode_of_a_window_layer(compile_for_chip, group):
    pa = _kernel("paged_attention")
    blocks, window = WINDOW_POOLS[group]
    pool = ((blocks, BLOCK, SDAR_KVH, D), bf16)
    rope_table = ((WINDOW_PAGES * BLOCK, D // 2), f32)
    text = compile_for_chip(
        lambda q, k_new, v_new, kp, vp, table, pos, cos, sin:
        pa.fused_paged_decode(
            q, k_new, v_new, kp, vp, table, pos,
            # a full layer of that model has no position encoding
            cos if window else None, sin if window else None,
            use_pallas=True, interpret=False, window=window),
        ((WINDOW_SLOTS, 1, H, D), bf16), ((WINDOW_SLOTS, 1, SDAR_KVH, D), bf16),
        ((WINDOW_SLOTS, 1, SDAR_KVH, D), bf16), pool, pool,
        ((WINDOW_SLOTS, WINDOW_PAGES), i32), ((WINDOW_SLOTS,), i32),
        rope_table, rope_table)
    assert "tpu_custom_call" in text and "fused_paged_decode" in text


@pytest.mark.parametrize("group", sorted(WINDOW_POOLS))
def test_fused_chunked_attention_of_a_window_layer(compile_for_chip, group):
    cp = _kernel("chunked_prefill")
    blocks, window = WINDOW_POOLS[group]
    pool = ((blocks, BLOCK, SDAR_KVH, D), bf16)
    text = compile_for_chip(
        lambda q, kp, vp, table, pos: cp.fused_chunked_attention(
            q, kp, vp, table, pos, use_pallas=True, interpret=False,
            window=window),
        ((1, CHUNK, H, D), bf16), pool, pool, ((1, WINDOW_PAGES), i32),
        ((1,), i32))
    assert "tpu_custom_call" in text and "fused_chunked_prefill" in text


# that model's other shapes: hidden 2048 into q and the gate (4096), the
# dense MLP (6144) and the shared expert (1024) behind a folded norm; 128
# experts of width 1024 over the 16 rows of a decode run and a chunk's 256
@pytest.mark.parametrize("rows", [WINDOW_SLOTS, CHUNK])
@pytest.mark.parametrize("width,act", [(4096, "none"), (6144, "silu"),
                                       (1024, "silu")])
def test_fused_norm_linear_at_hidden_2048(compile_for_chip, rows, width, act):
    fnl = _kernel("fused_norm_linear")
    text = compile_for_chip(
        lambda x, nw, w: fnl.fused_rmsnorm_linear(
            x, nw, w, 1e-5, activation=act),
        ((rows, SDAR_HIDDEN), bf16), ((SDAR_HIDDEN,), bf16),
        ((SDAR_HIDDEN, width), bf16))
    _one_bf16_product(text, rows, SDAR_HIDDEN, width)


@pytest.mark.parametrize("tokens", [WINDOW_SLOTS, CHUNK],
                         ids=["decode", "chunk"])
def test_grouped_experts_behind_a_sigmoid_router(compile_for_chip, tokens):
    me = _kernel("moe_experts")

    def experts(x, router, bias, wg, wu, wd):
        chosen, gates = me.route_topk(x, router, TOP_K, scores="sigmoid",
                                      bias=bias, scale=2.826,
                                      norm_eps=1e-20)
        out, stats = me.grouped_experts(x, chosen, gates, wg, wu, wd,
                                        use_pallas=True, interpret=False)
        return out, stats.as_vector()

    w = ((EXPERTS, 1024, SDAR_HIDDEN), bf16)
    text = compile_for_chip(experts, ((tokens, SDAR_HIDDEN), bf16),
                            ((SDAR_HIDDEN, EXPERTS), bf16),
                            ((EXPERTS,), f32), w, w, w)
    assert "tpu_custom_call" in text and "moe_grouped_experts" in text
    assert " while(" not in text


# ---- the two latent-attention kernels at the widths of the model with
# latent (MLA) pages that the benchmark serves: 20 heads, an entry of 576
# numbers (512 of them the value) in 640 lanes, block 16, the pool's
# 24,576 blocks, 8 slots over a 2,048-page table (32 k tokens: 8 x 2,048
# table entries ride in as ONE scalar-prefetch operand), a chunk of 256
LATENT_HEADS, LATENT_ENTRY, LATENT_VALUE = 20, 576, 512
LATENT_SLOTS, LATENT_PAGES, LATENT_BLOCKS = 8, 2048, 24576


def _latent_pool(la):
    return ((LATENT_BLOCKS, BLOCK, la.latent_pool_lanes(LATENT_ENTRY)), bf16)


def test_fused_latent_decode(compile_for_chip):
    la = _kernel("latent_attention")
    pool = _latent_pool(la)
    lanes = pool[0][-1]
    assert lanes == 640
    text = compile_for_chip(
        lambda q, entry, pages, table, pos: la.fused_latent_decode(
            q, entry, pages, table, pos, value_lanes=LATENT_VALUE,
            use_pallas=True, interpret=False),
        ((LATENT_SLOTS, LATENT_HEADS, lanes), bf16),
        ((LATENT_SLOTS, lanes), bf16), pool,
        ((LATENT_SLOTS, LATENT_PAGES), i32), ((LATENT_SLOTS,), i32))
    assert "tpu_custom_call" in text and "fused_latent_decode" in text


def test_fused_latent_chunk(compile_for_chip):
    la = _kernel("latent_attention")
    pool = _latent_pool(la)
    text = compile_for_chip(
        lambda q, pages, table, pos: la.fused_latent_chunk(
            q, pages, table, pos, value_lanes=LATENT_VALUE,
            use_pallas=True, interpret=False),
        ((1, CHUNK, LATENT_HEADS, pool[0][-1]), bf16), pool,
        ((1, LATENT_PAGES), i32), ((1,), i32))
    assert "tpu_custom_call" in text and "fused_latent_chunk" in text


def test_a_page_of_576_lanes_is_refused_by_the_chip_s_compiler(
        compile_for_chip):
    """Why the pool pads a latent entry to 640 lanes: Mosaic slices a
    page only at whole 128-lane tiles."""
    la = _kernel("latent_attention")
    with pytest.raises(Exception, match="aligned to tiling"):
        compile_for_chip(
            lambda q, pages, table, pos: la.fused_latent_chunk(
                q, pages, table, pos, value_lanes=LATENT_VALUE,
                use_pallas=True, interpret=False),
            ((1, CHUNK, LATENT_HEADS, LATENT_ENTRY), bf16),
            ((LATENT_BLOCKS, BLOCK, LATENT_ENTRY), bf16),
            ((1, LATENT_PAGES), i32), ((1,), i32))


# that model's other shapes: hidden 2048 into the low-rank query (768) and
# the latent entry (576), the rank into 20 heads of 256 (5120), the dense
# MLP (10240) and the shared expert (1536) behind a folded norm; 64 experts
# of width 1536 over the 8 rows of a decode run and a chunk's 256
@pytest.mark.parametrize("rows", [LATENT_SLOTS, CHUNK])
@pytest.mark.parametrize("k,width,act", [
    (SDAR_HIDDEN, 768, "none"), (768, 5120, "none"),
    (SDAR_HIDDEN, LATENT_ENTRY, "none"),
    (SDAR_HIDDEN, 10240, "silu"), (SDAR_HIDDEN, 1536, "silu")])
def test_fused_norm_linear_at_the_latent_model_s_widths(
        compile_for_chip, rows, k, width, act):
    fnl = _kernel("fused_norm_linear")
    text = compile_for_chip(
        lambda x, nw, w: fnl.fused_rmsnorm_linear(
            x, nw, w, 1e-5, activation=act),
        ((rows, k), bf16), ((k,), bf16), ((k, width), bf16))
    _one_bf16_product(text, rows, k, width)


@pytest.mark.parametrize("tokens", [LATENT_SLOTS, CHUNK],
                         ids=["decode", "chunk"])
def test_grouped_experts_at_64_experts_of_width_1536(compile_for_chip,
                                                     tokens):
    me = _kernel("moe_experts")

    def experts(x, router, bias, wg, wu, wd):
        chosen, gates = me.route_topk(x, router, 4, scores="sigmoid",
                                      bias=bias, scale=1.8, norm_eps=1e-20)
        out, stats = me.grouped_experts(x, chosen, gates, wg, wu, wd,
                                        use_pallas=True, interpret=False)
        return out, stats.as_vector()

    w = ((64, 1536, SDAR_HIDDEN), bf16)
    text = compile_for_chip(experts, ((tokens, SDAR_HIDDEN), bf16),
                            ((SDAR_HIDDEN, 64), bf16), ((64,), f32), w, w, w)
    assert "tpu_custom_call" in text and "moe_grouped_experts" in text
    assert " while(" not in text
