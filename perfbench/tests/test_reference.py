"""The plain reference against the program's own forward at a tiny size on
the CPU, on the benchmark's seeded weights."""

import jax.numpy as jnp
import numpy as np

from perfbench import common, weights
from perfbench.reference import mistral

CONFIG = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 192,
          "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 16, "max_position_embeddings": 128, "rope_theta": 1e6,
          "rms_norm_eps": 1e-5, "tie_word_embeddings": False}
SEED = 3_000_000_019


def _program_logits(ids):
    from accelerate_tpu.big_modeling import init_empty_weights
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    with init_empty_weights():
        model = LlamaForCausalLM.from_config(LlamaConfig(**common.llama_keys(CONFIG), remat=False))
    params = weights.make_tree(SEED, model.params)
    return np.asarray(model.apply_fn(params, input_ids=jnp.asarray(ids)[None])["logits"][0])


def test_reference_matches_llama_apply():
    ids = np.random.default_rng(0).integers(0, 256, size=40).astype(np.int32)
    want = _program_logits(ids)
    padded = np.zeros((128,), np.int32)
    padded[:40] = ids
    got = np.asarray(mistral.logits_at(CONFIG, SEED, padded, 40, np.arange(40), "float32"))
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_weights_one_layer_alone_equals_its_slice_of_the_stack():
    key = weights.root_key(SEED)
    shape = mistral.leaf_shapes(CONFIG)["layers.wq"]
    stack = weights.leaf(key, "layers.wq", shape, jnp.float32)
    one = weights.leaf(key, "layers.wq", shape, jnp.float32, layer=1)
    assert stack.shape == shape and np.array_equal(np.asarray(stack[1]), np.asarray(one))
    other = weights.leaf(weights.root_key(SEED + 1), "layers.wq", shape, jnp.float32, layer=1)
    assert not np.array_equal(np.asarray(one), np.asarray(other))


def test_a_configurations_weight_scales_reach_program_and_reference_alike():
    scaled = dict(CONFIG, weight_scales={"layers.wq": 3.0})
    key = weights.root_key(SEED)
    shape = mistral.leaf_shapes(CONFIG)["layers.wq"]
    plain = weights.leaf(key, "layers.wq", shape, jnp.float32)
    big = weights.leaf(key, "layers.wq", shape, jnp.float32, scales=scaled["weight_scales"])
    assert np.allclose(np.asarray(big), 3.0 * np.asarray(plain))
    ids = np.random.default_rng(1).integers(0, 256, size=128).astype(np.int32)
    a = np.asarray(mistral.logits_at(CONFIG, SEED, ids, 128, np.arange(128), "float32"))
    b = np.asarray(mistral.logits_at(scaled, SEED, ids, 128, np.arange(128), "float32"))
    assert np.abs(a - b).max() > 1e-2 * a.std()  # sharper attention moves the logits


def test_the_check_reads_logprobs_that_sum_to_one_and_the_served_tokens_gap():
    from perfbench import check

    ids = np.random.default_rng(2).integers(0, 256, size=50).astype(np.int32)
    prompt, tokens = ids[:30], ids[30:]
    out = check.sequence_readings(CONFIG, SEED, prompt, tokens, "float32")
    assert out["gaps"].shape == (20,) and (out["gaps"] >= 0).all()
    assert (out["logprobs"] <= 0).all() and out["logit_std"] > 0
    padded = np.zeros((128,), np.int32)
    padded[:49] = ids[:49]
    logits = np.asarray(mistral.logits_at(CONFIG, SEED, padded, 49, np.arange(29, 49), "float32"),
                        np.float64)
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    assert np.allclose(out["logprobs"], lp[np.arange(20), tokens], atol=1e-5)
