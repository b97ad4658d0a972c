"""The KV pool stays where it is (ISSUE 25): the compiled decode and
prefill programs take the stacked pool donated, update it by row scatters
at ``(layer, block, offset)``, read it at ``(layer, block)``, and hand the
same buffer back. Nothing of the pool's size, and nothing of one layer's
slab, is produced inside them — no slice, no reshape, no write-back, no
copy — which :func:`accelerate_tpu.utils.hlo.buffers_moved` reads off the
compiled text. ``chip_smoke.py`` runs the same reading on the chip.

All tier-1 and cheap: on the CPU a three-layer tiny model, one prefill
chunk and one decode burst per engine, f32 and int8 pools (XLA's CPU
backend has no bf16 scatter: it converts a bf16 pool to f32 and back around
each one, which is that compiler's doing and says nothing of the chip); and
the paged step at Mistral-7B widths compiled for a described v5e chip, bf16
and int8 pools, with the Pallas kernel in it — no chip is needed to compile.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
from accelerate_tpu.serving import EngineConfig, InferenceEngine
from accelerate_tpu.utils.hlo import buffers_moved


@pytest.fixture(scope="module")
def tiny_model():
    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=3, heads=4, seq=96)
    return LlamaForCausalLM.from_config(config, seed=0)


#: 401 blocks: one layer's slab of the pool is larger than all the
#: activations of the tiny model together, and no weight or activation
#: shares an element count with the pool, a slab of it, or their scale arrays
GEOM = dict(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8,
            decode_burst=2, num_blocks=401)


def _served_engine(model, kv_dtype, draft):
    spec = dict(spec_k=2, draft=draft) if draft else {}
    engine = InferenceEngine(model, EngineConfig(kv_dtype=kv_dtype, **GEOM, **spec))
    rng = np.random.default_rng(0)
    request = engine.add_request(rng.integers(0, 64, size=11).astype(np.int32), 5)
    engine.run_until_idle(max_iterations=200)
    assert len(request.output_tokens) == 5
    return engine


def _watched(engine):
    """Element counts of each pool array and of one layer's slab of it."""
    pools = [p for p in (engine._kp, engine._ks) if p is not None]
    return sorted({n for p in pools for n in (p.size, p.size // p.shape[0])})


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("draft", [None, "early_exit:1"])
@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_compiled_step_leaves_the_pool_in_place(tiny_model, kv_dtype, draft, program):
    engine = _served_engine(tiny_model, kv_dtype, draft)
    compiled = engine.compiled(program)
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_{'spec' if draft and program == 'decode' else program}")
    found = buffers_moved(text, _watched(engine))
    # (a) nothing of pool or slab size but parameters, plumbing and the
    # in-place row scatters; (b) every pool parameter aliased to an output
    assert found["moved"] == []
    assert found["unaliased"] == []
    # the scatters are there (two per pool array and layer loop), and the
    # whole pool is what they update
    pool_shape = "[" + ",".join(map(str, engine._kp.shape)) + "]"
    assert sum(
        1 for line in text.splitlines()
        if " scatter(" in line and pool_shape in line.split(" scatter(")[0]
    ) >= 2
    # the executable holds no second pool: its temporaries are smaller than
    # one layer's slab of one pool
    slab_bytes = engine._kp.nbytes // engine._kp.shape[0]
    assert compiled.memory_analysis().temp_size_in_bytes < slab_bytes


def test_pool_is_allocated_in_the_layout_the_kernel_reads(tiny_model):
    """Heads fold into lanes in storage, so the kernel's view is the stored
    view and no program relayouts the pool."""
    engine = InferenceEngine(tiny_model, EngineConfig(kv_dtype="int8", **GEOM))
    cfg = tiny_model.config
    assert engine._kp.shape == (
        3, 401, 8, cfg.num_key_value_heads * cfg.head_dim
    )
    assert engine._ks.shape == (3, 401, 8, cfg.num_key_value_heads)


def test_the_check_sees_a_pool_scanned_as_xs_and_ys():
    """The negative control: the carriage this PR removed — the stacked
    pool as a scan's ``xs`` and ``ys`` — slices a slab out per layer and
    writes it back into a second buffer, and ``buffers_moved`` says so."""
    pool = jnp.zeros((3, 101, 8, 32), jnp.float32)
    rows = jnp.ones((3, 4, 32), jnp.float32)
    blk = jnp.asarray([1, 2, 3, 4], jnp.int32)

    def scanned(pool, rows):
        def body(carry, xs):
            slab, r = xs
            slab = slab.at[blk, 0].set(r)
            return carry + slab[blk, 0].sum(), slab
        return jax.lax.scan(body, jnp.float32(0), (pool, rows))

    def carried(pool, rows):
        def body(carry, xs):
            acc, pool = carry
            r, layer = xs
            pool = pool.at[layer, blk, 0].set(r)
            return (acc + pool[layer, blk, 0].sum(), pool), None
        (acc, pool), _ = jax.lax.scan(
            body, (jnp.float32(0), pool), (rows, jnp.arange(3, dtype=jnp.int32))
        )
        return acc, pool

    watched = [pool.size, pool.size // 3]
    text = lambda fn: jax.jit(fn, donate_argnums=(0,)).lower(pool, rows).compile().as_text()
    old = buffers_moved(text(scanned), watched)
    assert old["moved"], "the scanned pool should show slab-sized instructions"
    new = buffers_moved(text(carried), watched)
    assert new == {"moved": [], "unaliased": []}


_MODULE = """HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias) }, entry_computation_layout={(f32[4,8]{1,0}, f32[4,8]{1,0})->(f32[4,8]{1,0}, f32[4,8]{1,0})}

%fused_scatter (p0: f32[4,8], p1: s32[2], p2: f32[2,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p1 = s32[2]{0} parameter(1)
  %p2 = f32[2,8]{1,0} parameter(2)
  ROOT %scatter.1 = f32[4,8]{1,0} scatter(%p0, %p1, %p2), to_apply=%add
}

%fused_slice (p0: f32[4,8]) -> f32[2,8] {
  %p0.1 = f32[4,8]{1,0} parameter(0)
  ROOT %dynamic-slice.1 = f32[2,8]{1,0} dynamic-slice(%p0.1, %c, %c), dynamic_slice_sizes={2,8}
}

%fused_slab (p0: f32[4,8]) -> f32[1,8] {
  %p0.2 = f32[4,8]{1,0} parameter(0)
  ROOT %slice.1 = f32[1,8]{1,0} slice(%p0.2), slice={[0:1], [0:8]}
}

ENTRY %main (a: f32[4,8], b: f32[4,8]) -> (f32[4,8], f32[4,8]) {
  %a = f32[4,8]{1,0:T(8,128)} parameter(0)
  %b = f32[4,8]{1,0:T(8,128)} parameter(1)
  %fusion.1 = f32[4,8]{1,0:T(8,128)} fusion(%a, %i, %u), kind=kLoop, calls=%fused_scatter
EXTRA
  ROOT %tuple.1 = (f32[4,8]{1,0}, f32[4,8]{1,0}) tuple(%fusion.1, %b)
}
"""


@pytest.mark.parametrize(
    "line, moved",
    [
        ("", []),
        ("  %copy.3 = f32[4,8]{0,1:T(8,128)} copy(%b)", [("copy.3", "copy", "f32[4,8]")]),
        ("  %fusion.2 = f32[2,8]{1,0} fusion(%b), kind=kLoop, calls=%fused_slice", []),
        (
            "  %fusion.3 = f32[1,8]{1,0} fusion(%b), kind=kLoop, calls=%fused_slab",
            [("fusion.3", "fusion:slice", "f32[1,8]")],
        ),
        (
            "  %copy-start.1 = (f32[4,8]{1,0:T(8,128)S(1)}, f32[4,8]{1,0}, u32[]{:S(2)}) copy-start(%b)",
            [("copy-start.1", "copy-start", "f32[4,8]")],
        ),
        ("  %reshape.9 = f32[8,4]{1,0} reshape(%b)", [("reshape.9", "reshape", "f32[8,4]")]),
        ("  %bitcast.2 = f32[32]{0} bitcast(%b)", []),
        ("  %dus.1 = f32[4,8]{1,0} dynamic-update-slice(%b, %u, %c, %c)", []),
        ("  %small.1 = f32[2,8]{1,0} copy(%u)", []),
    ],
)
def test_buffers_moved_reads_compiled_text(line, moved):
    """The reader on a module written out by hand: a whole-buffer size (32
    elements) and a slab's (8) are watched; parameter 0 is aliased in the
    header and parameter 1 is not."""
    found = buffers_moved(_MODULE.replace("EXTRA", line), [32, 8])
    # the slab-sized slice inside %fused_slab is itself a finding, whether
    # or not the entry computation calls it
    assert found["moved"] == [("slice.1", "slice", "f32[1,8]")] + moved
    assert found["unaliased"] == [1]


# ---------------------------------------------------------------------------
# the same reading of the program the TPU's compiler makes (no chip needed)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_v5e_compiled_step_leaves_the_pool_in_place(one_chip, monkeypatch, kv_dtype, program):
    """Mistral-7B widths (GQA 32/8 heads of 128, block 16), two layers, 64
    slots x 256 table entries as the benchmark's chat cell dispatches them,
    or a 128-token chunk (the cell's 256 sits at the edge of the kernel's
    VMEM, and whether it fits is not this test's matter): compiled for the v5e, the Pallas kernel is in the
    program, the K and V pools are aliased through it, nothing of their
    size or of one layer's slab is produced, and the temporaries are far
    smaller than a slab (the parent held a second copy of both pools)."""
    import sys

    from accelerate_tpu.models.llama import init_llama_params, llama_apply

    monkeypatch.setattr(
        sys.modules["accelerate_tpu.ops.paged_attention"],
        "default_paged_attention_impl", lambda: "pallas",
    )
    # 3000 blocks: a slab of 49,152,000 elements, which no weight has
    layers, blocks, bs, slots, table, chunk = 2, 3000, 16, 64, 256, 128
    c = LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=layers, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=32768, rope_theta=1e6, tie_word_embeddings=False,
    )
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: shaped(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_llama_params(jax.random.PRNGKey(0), c)),
    )
    quantized = kv_dtype == "int8"
    pool = shaped((layers, blocks, bs, 8 * 128), jnp.int8 if quantized else jnp.bfloat16)
    pools = {"k": pool, "v": pool}
    if quantized:
        pools["k_scale"] = pools["v_scale"] = shaped((layers, blocks, bs, 8), jnp.float32)

    def step(params, pools, tables, pos, toks, mask):
        out = llama_apply(
            c, params, input_ids=toks, paged_kv=pools, block_tables=tables,
            cache_positions=pos, paged_write_mask=mask,
        )
        return out["paged_kv"], jnp.argmax(out["logits"][:, -1, :], -1).astype(jnp.int32)

    def decode(params, pools, tables, pos, toks, mask):
        def one(carry, _):
            pools, toks, pos = carry
            pools, tok = step(params, pools, tables, pos, toks, mask)
            return (pools, tok[:, None], pos + 1), tok
        (pools, _, _), out = jax.lax.scan(one, (pools, toks, pos), None, length=4)
        return pools, out

    b, s, fn = (slots, 1, decode) if program == "decode" else (1, chunk, step)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, shaped((b, table), jnp.int32), shaped((b,), jnp.int32),
        shaped((b, s), jnp.int32), shaped((b, s), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    numel = layers * blocks * bs * 8 * 128
    found = buffers_moved(text, [numel, numel // layers])
    assert found == {"moved": [], "unaliased": []}
    # a quarter of one layer's bf16 slab; for int8 only "less than the two
    # pools", the second copy the parent held: its scale arrays are another
    # matter - the compiler keeps them in a layout of its own inside the
    # loop and relayouts them, lanes padded 8 -> 128, for the kernel
    # (PERF.md section 7)
    limit = 2 * numel if quantized else numel // layers * 2 // 4
    assert compiled.memory_analysis().temp_size_in_bytes < limit


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_v5e_compiled_hybrid_step_leaves_the_slot_state_in_place(one_chip, monkeypatch, program):
    """Granite-4.0-H-Micro's widths (Mamba-2: 64 heads of 64, state 128;
    attention: 32/8 heads of **64**, the paged kernel's half-vreg lane
    slices), six layers ``mamba x 5, attention``, 48 slots (with 64 a slot's
    rows of one layer are as many elements as one MLP matrix), the vocabulary
    cut to 8192: compiled for the v5e, both kernels are in the program, the
    float32 state ``[5, 48, 64, 64, 128]`` is aliased through
    ``ssm_state_update`` at ``(layer, slot)``, no other instruction
    produces anything of its size or of one layer's slab of it, and the
    temporaries are far smaller than a slab (503 MB)."""
    import sys

    from accelerate_tpu.models import granite_hybrid as gh

    monkeypatch.setattr(
        sys.modules["accelerate_tpu.ops.paged_attention"],
        "default_paged_attention_impl", lambda: "pallas",
    )
    monkeypatch.setattr(sys.modules["accelerate_tpu.ops.ssm"], "default_ssm_impl", lambda: "pallas")
    slots, blocks, bs, table, chunk = 48, 3000, 16, 256, 256
    c = gh.GraniteHybridConfig(
        vocab_size=8192, num_hidden_layers=6, layer_types=("mamba",) * 5 + ("attention",))
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: shaped(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: gh.init_granite_hybrid_params(jax.random.PRNGKey(0), c)),
    )
    pool = shaped((1, blocks, bs, 8 * 64), jnp.bfloat16)
    cache = {"k": pool, "v": pool}
    for name, leaf in gh.cache_spec(c).slot_state.items():
        cache[name] = shaped(leaf.array_shape(slots), leaf.dtype or jnp.bfloat16)
    assert cache["ssm"].shape == (5, 48, 64, 64, 128) and cache["ssm"].dtype == jnp.float32

    def step(params, cache, tables, pos, toks, mask, state_slots=None):
        out = gh.granite_hybrid_apply(
            c, params, toks, paged_kv=cache, block_tables=tables,
            cache_positions=pos, paged_write_mask=mask, state_slots=state_slots,
        )
        return out["paged_kv"], jnp.argmax(out["logits"][:, -1, :], -1).astype(jnp.int32)

    def decode(params, cache, tables, pos, toks, mask):
        def one(carry, _):
            cache, toks, pos = carry
            cache, tok = step(params, cache, tables, pos, toks, mask)
            return (cache, tok[:, None], pos + 1), tok
        (cache, _, _), out = jax.lax.scan(one, (cache, toks, pos), None, length=4)
        return cache, out

    b, s = (slots, 1) if program == "decode" else (1, chunk)
    operands = [params, cache, shaped((b, table), jnp.int32), shaped((b,), jnp.int32),
                shaped((b, s), jnp.int32), shaped((b, s), jnp.bool_)]
    if program == "prefill":
        operands.append(shaped((1,), jnp.int32))
    compiled = jax.jit(decode if program == "decode" else step, donate_argnums=(1,)).lower(
        *operands).compile()
    text = compiled.as_text()
    # one run of Mamba layers and one attention layer: a kernel each in the
    # decode program; a prefill chunk scans in plain einsums (scope ssm_scan)
    assert text.count('custom_call_target="tpu_custom_call"') == (2 if program == "decode" else 1)
    state = 5 * 48 * 64 * 64 * 128
    found = buffers_moved(text, [state, state // 5])
    in_place = {"custom-call", "scatter", "fusion:scatter"}
    assert [m for m in found["moved"] if m[1] not in in_place] == [], found["moved"]
    assert found["unaliased"] == []
    assert compiled.memory_analysis().temp_size_in_bytes < state * 4 // 5 // 4


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_v5e_compiled_routed_step_addresses_the_expert_stacks_in_place(one_chip, monkeypatch, program):
    """LFM2-8B-A1B's widths (32 experts of 2048 x 1792, top 4; 32 / 8 heads
    of 64; a 3-tap convolution's two-row tail), three layers ``conv (dense),
    full_attention, conv`` with the last two routed, 64 slots, the
    vocabulary cut to 8192: compiled for the v5e, the grouped Pallas product
    is in the program twice a routed layer beside the paged kernel, and no
    instruction produces anything of the size of an expert stack ``[2, 32,
    2048, 3584]`` / ``[2, 32, 1792, 2048]`` or of one layer's slab of it:
    ``(layer, expert)`` is addressed through the kernel's index maps."""
    import sys

    from accelerate_tpu.models import lfm2

    monkeypatch.setattr(
        sys.modules["accelerate_tpu.ops.paged_attention"],
        "default_paged_attention_impl", lambda: "pallas",
    )
    monkeypatch.setattr(sys.modules["accelerate_tpu.ops.moe"], "default_moe_impl", lambda: "gmm")
    slots, blocks, bs, table, chunk = 64, 3000, 16, 256, 256
    c = lfm2.Lfm2MoeConfig(
        vocab_size=8192, num_hidden_layers=3, num_dense_layers=1,
        layer_types=("conv", "full_attention", "conv"))
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: shaped(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: lfm2.init_lfm2_params(jax.random.PRNGKey(0), c)),
    )
    pool = shaped((1, blocks, bs, 8 * 64), jnp.bfloat16)
    cache = {"k": pool, "v": pool}
    for name, leaf in lfm2.cache_spec(c).slot_state.items():
        cache[name] = shaped(leaf.array_shape(slots), leaf.dtype or jnp.bfloat16)
    assert cache["conv"].shape == (2, 64, 2, 2048)

    def step(params, cache, tables, pos, toks, mask, state_slots=None):
        out = lfm2.lfm2_apply(
            c, params, toks, paged_kv=cache, block_tables=tables,
            cache_positions=pos, paged_write_mask=mask, state_slots=state_slots,
        )
        return (out["paged_kv"], jnp.argmax(out["logits"][:, -1, :], -1).astype(jnp.int32),
                out["step_counters"])

    b, s = (slots, 1) if program == "decode" else (1, chunk)
    operands = [params, cache, shaped((b, table), jnp.int32), shaped((b,), jnp.int32),
                shaped((b, s), jnp.int32), shaped((b, s), jnp.bool_)]
    if program == "prefill":
        operands.append(shaped((1,), jnp.int32))
    # the serving default: another test file sets "highest" for the whole
    # process as it is imported, which the grouped product's bfloat16
    # kernel cannot be compiled under
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(*operands).compile()
    text = compiled.as_text()
    # two routed layers x two grouped products, one attention layer's kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    w_in, w_out = 2 * 32 * 2048 * 3584, 2 * 32 * 1792 * 2048
    found = buffers_moved(text, [w_in, w_in // 2, w_out, w_out // 2])
    assert found["moved"] == [], found["moved"]
    # the temporaries are far smaller than one expert's matrices
    assert compiled.memory_analysis().temp_size_in_bytes < 2048 * 3584 * 2


@pytest.mark.parametrize("program", ["round", "prefill"])
def test_v5e_compiled_block_step_walks_to_the_blocks_end(one_chip, monkeypatch, program):
    """SDAR-30B-A3B-Chat's widths (128 experts of 2048 x 768, top 8 of a
    softmax; 32 / 4 heads of 128), two layers, 64 slots, the vocabulary cut
    to 8192: one forward of a block round (``[64, 4]`` rows, ``block_len``
    4) and a prefill chunk compile for the v5e with the paged kernel (its
    mask and trip count under ``block_len``) and the grouped product at
    ``E = 128``, ``f = 768`` in the program, and no instruction produces
    anything of the size of an expert stack or of one layer's slab of it."""
    import sys

    from accelerate_tpu.models import sdar_moe

    monkeypatch.setattr(
        sys.modules["accelerate_tpu.ops.paged_attention"],
        "default_paged_attention_impl", lambda: "pallas",
    )
    monkeypatch.setattr(sys.modules["accelerate_tpu.ops.moe"], "default_moe_impl", lambda: "gmm")
    slots, blocks, bs, table, chunk = 64, 3000, 16, 256, 256
    c = sdar_moe.SdarMoeConfig(vocab_size=8192, num_hidden_layers=2, mask_token_id=8191)
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: shaped(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: sdar_moe.init_sdar_params(jax.random.PRNGKey(0), c)),
    )
    pool = shaped((2, blocks, bs, 4 * 128), jnp.bfloat16)

    def step(params, cache, tables, pos, toks, mask):
        if program == "prefill":  # as the engine runs it: no logits are read (ISSUE 46)
            out = sdar_moe.sdar_apply(
                c, params, toks, paged_kv=cache, block_tables=tables,
                cache_positions=pos, paged_write_mask=mask)
            return out["paged_kv"], out["step_counters"]
        # a denoise pass at denoise_steps 2: the logits of its sub-block of every slot
        rows = jnp.broadcast_to(jnp.arange(2, dtype=jnp.int32), (slots, 2))
        out = sdar_moe.sdar_apply(
            c, params, toks, paged_kv=cache, block_tables=tables,
            cache_positions=pos, paged_write_mask=mask, logit_positions=rows,
        )
        return (out["paged_kv"], jnp.argmax(out["logits"], -1).astype(jnp.int32),
                out["step_counters"])

    b, s = (slots, c.block_length) if program == "round" else (1, chunk)
    operands = [params, {"k": pool, "v": pool}, shaped((b, table), jnp.int32),
                shaped((b,), jnp.int32), shaped((b, s), jnp.int32), shaped((b, s), jnp.bool_)]
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(*operands).compile()
    text = compiled.as_text()
    # two layers x (the paged kernel + two grouped products); nobody reads what
    # the last layer of a block model's chunk hands on, so its two products go
    # with the head (its keys and values are written before them)
    assert text.count('custom_call_target="tpu_custom_call"') == (6 if program == "round" else 4)
    # the head on the rows that are read: 128 of a pass's 256, none of a chunk's
    # ([2048, 8192] is the head's matrix, a parameter)
    vocab_wide = set(re.findall(r"\[[0-9,]*8192\]", text)) - {"[2048,8192]"}
    if program == "round":
        assert vocab_wide & {"[128,8192]", "[64,2,8192]"}
        assert not vocab_wide & {"[256,8192]", "[64,4,8192]"}
    else:
        assert vocab_wide == set()
    w_in, w_out = 2 * 128 * 2048 * 1536, 2 * 128 * 768 * 2048
    found = buffers_moved(text, [w_in, w_in // 2, w_out, w_out // 2, 2 * blocks * bs * 512,
                                 blocks * bs * 512])
    assert found["moved"] == [], found["moved"]


@pytest.mark.parametrize("program", ["decode", "prefill", "decode_fp8"])
def test_v5e_compiled_latent_step_leaves_the_one_pool_in_place(one_chip, monkeypatch, program):
    """DeepSeek-V3's widths (128 heads absorbed over a 576-value latent row
    stored 640 wide; 16 held experts of 7168 x 2048 under the 256-wide grouped
    router, a shared expert), one dense and one routed layer, 40 slots x 1,024
    table entries as the benchmark's cell dispatches them, the vocabulary cut
    to 8192: a decode step, a 512-token chunk and a decode step over an fp8
    pool compile for the v5e with the ``latent_attention`` kernel and the
    grouped product (``k`` = 7168 at a contraction tile that divides it) in
    the program, the ONE pool aliased through it - nothing of its size or of a
    layer's slab is produced - and no instruction the size of an expert
    stack."""
    import sys

    from accelerate_tpu.models import deepseek_v3 as ds

    monkeypatch.setattr(
        sys.modules["accelerate_tpu.ops.paged_attention"],
        "default_paged_attention_impl", lambda: "pallas",
    )
    monkeypatch.setattr(sys.modules["accelerate_tpu.ops.moe"], "default_moe_impl", lambda: "gmm")
    slots, blocks, bs, table, chunk = 40, 3000, 16, 1024, 512
    c = ds.DeepseekV3Config(
        vocab_size=8192, num_hidden_layers=2, first_k_dense_replace=1, n_routed_experts=16,
        router_experts=256,
        rope_scaling={"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    spec = ds.cache_spec(c)
    assert (spec.pool_leaves, spec.pool_width) == (("k",), 640)
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: shaped(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: ds.init_deepseek_params(jax.random.PRNGKey(0), c)),
    )
    quantized = program == "decode_fp8"
    cache = {"k": shaped((2, blocks, bs, 640), jnp.float8_e4m3fn if quantized else jnp.bfloat16)}
    if quantized:
        cache["k_scale"] = shaped((2, blocks, bs, 1), jnp.float32)

    def step(params, cache, tables, pos, toks, mask):
        out = ds.deepseek_apply(
            c, params, toks, paged_kv=cache, block_tables=tables,
            cache_positions=pos, paged_write_mask=mask,
        )
        return (out["paged_kv"], jnp.argmax(out["logits"][:, -1, :], -1).astype(jnp.int32),
                out["step_counters"])

    b, s = (1, chunk) if program == "prefill" else (slots, 1)
    operands = [params, cache, shaped((b, table), jnp.int32), shaped((b,), jnp.int32),
                shaped((b, s), jnp.int32), shaped((b, s), jnp.bool_)]
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(*operands).compile()
    text = compiled.as_text()
    # two layers' latent kernel, the routed layer's two grouped products
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert text.count("latent_attention") >= 2
    pool, w_in, w_out = 2 * blocks * bs * 640, 16 * 7168 * 4096, 16 * 2048 * 7168
    assert buffers_moved(text, [pool, pool // 2]) == {"moved": [], "unaliased": []}
    assert buffers_moved(text, [w_in, w_out])["moved"] == []
    # the temporaries are activations (a chunk's 128 absorbed queries of 640, the
    # logits: 336 MB for the chunk), under half a layer's slab of the cell's pool (40,961 blocks: 839 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 420e6


@pytest.mark.parametrize("program", ["decode", "prefill", "decode_fp8"])
def test_v5e_compiled_two_kind_step_leaves_both_pools_in_place(one_chip, monkeypatch, program):
    """SmallThinker-21BA3B's widths (GQA 28/4 of 128; 64 ReLU experts of 2560
    x 768 behind a router that reads the layer's input), one period of its
    layers (full, window, window, window), 40 slots x 1,024 table entries a
    kind as the benchmark's cell dispatches them, the window kind's pool at
    the cell's 12,841 blocks, the vocabulary cut to 8192: a decode step, a
    1,024-token chunk and a decode step over fp8 pools compile for the v5e
    with the ``paged_attention`` kernel - the windowed walk at 4,096 in three
    layers of four - and the grouped product in the program, BOTH kinds'
    pools aliased through it, nothing of their size or of a layer's slab
    produced."""
    import sys

    from accelerate_tpu.models import smallthinker as st

    monkeypatch.setattr(
        sys.modules["accelerate_tpu.ops.paged_attention"],
        "default_paged_attention_impl", lambda: "pallas",
    )
    monkeypatch.setattr(sys.modules["accelerate_tpu.ops.moe"], "default_moe_impl", lambda: "gmm")
    slots, blocks, window_blocks, bs, table, chunk = 40, 3000, 40 * 321 + 1, 16, 1024, 1024
    c = st.SmallThinkerConfig(vocab_size=8192, num_hidden_layers=4, rope_layout=(0, 1, 1, 1),
                              sliding_window_layout=(0, 1, 1, 1))
    spec = st.cache_spec(c)
    assert spec.window_pools(slots, 16384, bs, chunk) == {"window": (321, window_blocks)}
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: shaped(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: st.init_smallthinker_params(jax.random.PRNGKey(0), c)),
    )
    quantized = program == "decode_fp8"
    dtype = jnp.float8_e4m3fn if quantized else jnp.bfloat16
    cache = {}
    for n, (kind, count) in enumerate(zip(spec.paged_kinds, (blocks, window_blocks))):
        for leaf in ("k", "v"):
            cache[kind.pool_leaf(leaf, n == 0)] = shaped((kind.layers, count, bs, 512), dtype)
            if quantized:
                cache[kind.pool_leaf(leaf + "_scale", n == 0)] = shaped(
                    (kind.layers, count, bs, 4), jnp.float32)

    def step(params, cache, tables, pos, toks, mask):
        # a chunk as the engine asks for it: the logits of one row (ISSUE 46)
        rows = jnp.full((1, 1), chunk - 1, jnp.int32) if program == "prefill" else None
        out = st.smallthinker_apply(
            c, params, toks, paged_kv=cache, block_tables=tables,
            cache_positions=pos, paged_write_mask=mask, logit_positions=rows,
        )
        return (out["paged_kv"], jnp.argmax(out["logits"][:, -1, :], -1).astype(jnp.int32),
                out["step_counters"])

    b, s = (1, chunk) if program == "prefill" else (slots, 1)
    operands = [params, cache, shaped((b, 2, table), jnp.int32), shaped((b,), jnp.int32),
                shaped((b, s), jnp.int32), shaped((b, s), jnp.bool_)]
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(*operands).compile()
    text = compiled.as_text()
    # four layers' paged kernel, and each layer's two grouped products
    assert text.count('custom_call_target="tpu_custom_call"') == 12
    assert text.count("paged_attention") >= 4
    full, window = blocks * bs * 512, 3 * window_blocks * bs * 512
    assert buffers_moved(text, [full, window, window // 3]) == {"moved": [], "unaliased": []}
    w_in, w_out = 64 * 2560 * 1536, 64 * 768 * 2560
    assert buffers_moved(text, [w_in, w_out, 3 * w_in, 3 * w_out])["moved"] == []
    # a chunk's head runs on the row that is read: no [chunk, vocab] array
    assert f"[{chunk},8192]" not in text and f"[1,{chunk},8192]" not in text
    # the temporaries are activations (a chunk's 6,144 pairs through the experts),
    # under a third of one window layer's slab of the cell's pool (210 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        320e6 if quantized else 210e6)


def test_v5e4_train_step_moves_no_head_sized_array_inside_the_loss_loop(topo):
    """The train cell's step (Mistral-7B widths, 8 x 4,096 tokens, bf16
    compute over float32 parameters, adamw, ``fsdp=4``, ``remat``; ONE
    layer) compiled for the four described chips the way the
    ``Accelerator`` compiles it: parameters placed by the model's own rules
    (``lm_head`` on its HIDDEN dimension) and no mesh context entered.
    GSPMD left alone gathers the whole head and all-reduces its whole
    gradient in every chunk of the loss's loop (the step before ISSUE 41,
    and the sweep before its layout was pinned); pinned, the loop holds the
    three products of a chunk and nothing that moves an array of the
    head's size."""
    import re

    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from accelerate_tpu.models.llama import LLAMA_PARTITION_RULES, init_llama_params, llama_apply
    from accelerate_tpu.ops.attention import attention_context
    from accelerate_tpu.parallel.sharding import infer_param_sharding, opt_state_sharding_like
    from accelerate_tpu.utils.dataclasses import MESH_AXIS_ORDER, FullyShardedDataParallelPlugin
    from accelerate_tpu.utils.hlo import loop_instructions

    c = LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=1, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=32768, rope_theta=1e6, tie_word_embeddings=False, remat=True,
    )
    mesh = Mesh(
        np.asarray(topo.devices).reshape(tuple(4 if ax == "fsdp" else 1 for ax in MESH_AXIS_ORDER)),
        MESH_AXIS_ORDER)
    abstract = jax.eval_shape(lambda: init_llama_params(jax.random.PRNGKey(0), c))
    placed = infer_param_sharding(
        abstract, mesh, FullyShardedDataParallelPlugin(), LLAMA_PARTITION_RULES)
    assert placed["lm_head"].spec == P("fsdp", "tp")  # tp is 1 here: the hidden dimension
    shaped = lambda tree, shardings: jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)
    params = shaped(abstract, placed)
    tx = optax.adamw(3e-4)
    opt_placed = opt_state_sharding_like(tx, params, placed, mesh)
    opt_state = shaped(jax.eval_shape(tx.init, params), opt_placed)
    ids = jax.ShapeDtypeStruct((8, 4096), jnp.int32,
                               sharding=NamedSharding(mesh, P(("dp", "fsdp"), None)))

    def step(params, opt_state, ids):
        def loss_fn(p):
            p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
            return llama_apply(c, p, input_ids=ids, labels=ids)["loss"].astype(jnp.float32)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # the CPU backend would pick the blockwise attention route, which does
    # not fit a chip at 4k tokens; the cell runs the flash kernels
    with attention_context(mesh=mesh, impl="flash"):
        text = jax.jit(step, donate_argnums=(0, 1), out_shardings=(placed, opt_placed, None)).lower(
            params, opt_state, ids).compile().as_text()

    collective = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
    head = c.hidden_size * c.vocab_size
    sweeps = 0
    for body, rows in loop_instructions(text).items():
        products = [r for r in rows if r[1] in ("dot", "convolution") and "head" in r[3]]
        if not products:
            continue  # a layer's or a kernel's loop
        sweeps += 1
        assert len(products) == 3, (body, products)
        moved = [r for r in rows if collective.match(r[1]) and r[2] >= head]
        assert not moved, (body, moved)
        # a chunk is 1,024 rows a chip, 8 x 512 positions gathered: the logits are [8, 512, 32768 / 4]
        assert max(r[2] for r in products) == 8 * 512 * 8192
    assert sweeps == 1
