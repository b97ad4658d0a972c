"""Core-lane smoke slice of the compile-heavy subsystems (VERDICT r4 #9):
ONE cheapest config per path — model train step, GPipe schedule, flash
attention, generation, quantization — so a green default ``make test``
actually touches the compiled truth. The full per-subsystem matrices stay
in the slow lane (``make test_slow``); nothing here is marked slow."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator, MeshPlugin
from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
from accelerate_tpu.state import AcceleratorState, GradientState


def _batch(b=4, s=16, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, size=(b, s)).astype(np.int32)
    return {"input_ids": ids, "labels": ids.copy()}


def _tiny_config(**kw):
    return LlamaConfig.tiny(vocab_size=128, hidden_size=32, layers=2, heads=2, seq=16, **kw)


def test_smoke_llama_train_step_reduces_loss():
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    accelerator = Accelerator()
    model, opt = accelerator.prepare(
        LlamaForCausalLM.from_config(_tiny_config(), seed=0), optax.adamw(3e-3)
    )
    batch = _batch()
    losses = []
    for _ in range(3):
        out = model(**batch)
        accelerator.backward(out.loss)
        opt.step()
        opt.zero_grad()
        losses.append(float(np.asarray(out.loss.force())))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_smoke_pipeline_pp2_loss_matches_dense():
    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.models.llama import init_llama_params, llama_apply
    from accelerate_tpu.ops.attention import attention_context

    c = _tiny_config()
    params = init_llama_params(jax.random.PRNGKey(0), c)
    batch = _batch()

    def loss_fn(p):
        return llama_apply(c, p, batch["input_ids"], labels=batch["labels"])["loss"]

    dense = float(loss_fn(params))
    mesh = build_mesh(MeshPlugin(pp=2))  # dp absorbs the remaining devices
    with attention_context(mesh=mesh), jax.set_mesh(mesh):
        piped = float(jax.jit(loss_fn)(params))
    assert piped == pytest.approx(dense, rel=1e-4)


def test_smoke_flash_attention_matches_blockwise():
    from accelerate_tpu.ops.flash_attention import blockwise_attention, flash_attention

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 32, 2, 16)), jnp.float32) for _ in range(3)
    )
    flash = flash_attention(q, k, v, causal=True, block_q=16, block_kv=16, interpret=True)
    block = blockwise_attention(q, k, v, causal=True, block_kv=16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(block), rtol=2e-4, atol=2e-4)


def test_smoke_generation_greedy():
    from accelerate_tpu.generation import generate

    model = LlamaForCausalLM.from_config(_tiny_config(), seed=0)

    def fn(**kw):
        return model.apply_fn(model.params, **kw)

    ids = np.zeros((1, 4), np.int32)
    out = generate(fn, ids, max_new_tokens=3)
    assert out.shape == (1, 7)
    assert np.all(out[:, :4] == ids)


def test_smoke_quantization_roundtrip():
    from accelerate_tpu.utils.quantization import (
        dequantize_array,
        dequantize_array_4bit,
        quantize_array,
        quantize_array_4bit,
    )

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    int8_err = float(jnp.max(jnp.abs(dequantize_array(quantize_array(w)) - w)))
    assert int8_err < 0.05, int8_err
    nf4_err = float(jnp.max(jnp.abs(dequantize_array_4bit(quantize_array_4bit(w)) - w)))
    assert nf4_err < 0.5, nf4_err
