"""``ops.layers.fused_cross_entropy``: the chunk sweep's value and gradients
against the plain loss, and how its loop lies on an ``fsdp`` mesh."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from accelerate_tpu.ops.attention import attention_context
from accelerate_tpu.ops.fp8 import dense
from accelerate_tpu.ops.layers import cross_entropy_loss, fused_cross_entropy
from accelerate_tpu.utils.dataclasses import MESH_AXIS_ORDER
from accelerate_tpu.utils.hlo import loop_instructions

B, S, H, V = 2, 64, 32, 128


def _inputs(dtype=jnp.float32, seed=0):
    kx, kw, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (B, S, H), dtype)
    head = (jax.random.normal(kw, (H, V)) * 0.2).astype(dtype)
    return x, head, jax.random.randint(kl, (B, S), 0, V)


def _tied(x, embed):
    return dense(x, embed.T)


#: name -> (labels of the case, chunk_tokens, dense_fn, head as dense_fn takes it, cotangent)
CASES = {
    "eight_chunks": (lambda l: l, 16, dense, lambda w: w, 1.0),
    "two_chunks": (lambda l: l, 64, dense, lambda w: w, 1.0),
    "one_chunk_plain_loss": (lambda l: l, 1024, dense, lambda w: w, 1.0),
    "default_dense_fn": (lambda l: l, 16, None, lambda w: w, 1.0),
    "ignored_rows": (lambda l: l.at[:, -5:].set(-100).at[0, 8:24].set(-100), 16, dense,
                     lambda w: w, 1.0),
    "a_chunk_with_none_valid": (lambda l: l.at[:, 16:32].set(-100), 16, dense, lambda w: w, 1.0),
    "none_valid": (lambda l: jnp.full_like(l, -100), 16, dense, lambda w: w, 1.0),
    "tied_head_as_transpose": (lambda l: l, 16, _tied, lambda w: w.T, 1.0),
    "cotangent_3": (lambda l: l.at[:, -1].set(-100), 16, dense, lambda w: w, 3.0),
    "cotangent_small": (lambda l: l, 32, dense, lambda w: w, 1.0 / 1024),
}


def _both(case, dtype):
    relabel, chunk_tokens, dense_fn, as_taken, cotangent = CASES[case]
    x, w, labels = _inputs(dtype)
    labels, head = relabel(labels), as_taken(w)
    plain_dense = dense_fn or jnp.matmul

    def fused(x, head):
        return cotangent * fused_cross_entropy(
            x, head, labels, chunk_tokens=chunk_tokens, dense_fn=dense_fn)

    def plain(x, head):
        return cotangent * cross_entropy_loss(plain_dense(x, head), labels)

    got = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(x, head)
    want = jax.value_and_grad(plain, argnums=(0, 1))(
        x.astype(jnp.float32), head.astype(jnp.float32))
    return got, want, cotangent


@pytest.mark.parametrize("case", CASES)
def test_value_and_gradients_match_the_plain_loss_in_float32(case):
    (v, (dx, dw)), (v0, (dx0, dw0)), cotangent = _both(case, jnp.float32)
    tol = 1e-6 * max(cotangent, 1.0)
    np.testing.assert_allclose(v, v0, rtol=1e-6, atol=tol)
    np.testing.assert_allclose(dx, dx0, rtol=1e-5, atol=tol)
    np.testing.assert_allclose(dw, dw0, rtol=1e-5, atol=tol)
    assert dx.dtype == jnp.float32 and dw.dtype == jnp.float32


@pytest.mark.parametrize("case", ["eight_chunks", "ignored_rows", "tied_head_as_transpose",
                                  "cotangent_3"])
def test_bfloat16_inputs_at_bfloat16_tolerance(case):
    """bf16 products, float32 log-softmax and a float32 ``dW`` sum: within a
    few bf16 roundings of the float32 loss of the same (rounded) inputs, and
    the gradients come back in the inputs' dtype."""
    (v, (dx, dw)), (v0, (dx0, dw0)), cotangent = _both(case, jnp.bfloat16)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16 and v.dtype == jnp.float32
    np.testing.assert_allclose(v, v0, rtol=2e-2)
    for got, want in ((dx, dx0), (dw, dw0)):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        assert np.abs(got - want).max() <= 3e-2 * np.abs(want).max()


def test_the_value_alone_needs_no_gradient_sweep():
    """Undifferentiated (evaluation), the primal sweep runs: one product a
    chunk and the same value."""
    x, w, labels = _inputs()
    f = jax.jit(lambda x, w: fused_cross_entropy(x, w, labels, chunk_tokens=16, dense_fn=dense))
    np.testing.assert_allclose(f(x, w), cross_entropy_loss(x @ w, labels), rtol=1e-6)
    assert f.lower(x, w).as_text().count("stablehlo.dot_general") == 1


def test_fp8_dense_keeps_working_under_the_sweep():
    """``dense_fn`` stays the one owner of the product: under
    ``fp8_autocast`` the sweep's three products are the fp8 recipe's
    (the train cell's control, which must go on failing its check)."""
    from accelerate_tpu.ops.fp8 import fp8_autocast

    x, w, labels = _inputs(jnp.bfloat16)

    def loss(x, w):
        return fused_cross_entropy(x, w, labels, chunk_tokens=16, dense_fn=dense)

    with fp8_autocast():
        v8, g8 = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        text = jax.jit(jax.grad(loss)).lower(x, w).as_text()
    v, g = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
    assert "f8E4M3FN" in text and "f8E5M2" in text
    assert np.isfinite(float(v8)) and abs(float(v8) - float(v)) < 0.1 * float(v)
    for a, b in zip(g8, g):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert 0 < np.abs(a - b).max() <= 0.5 * np.abs(b).max()


# -- on a mesh ----------------------------------------------------------------

MB, MS, MH, MV = 8, 128, 64, 512
_COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")


@pytest.fixture
def fsdp4():
    shape = tuple(4 if ax == "fsdp" else 1 for ax in MESH_AXIS_ORDER)
    return Mesh(np.asarray(jax.devices()[:4]).reshape(shape), MESH_AXIS_ORDER)


@pytest.mark.parametrize("head_dim", ["vocabulary", "hidden"])
def test_the_loop_moves_no_head_sized_array_on_an_fsdp_mesh(fsdp4, head_dim):
    """Rows on batch over ``fsdp``; the head sharded on its vocabulary (the
    fsdp default: the largest dimension) or on its hidden dimension (the
    llama rule). Either way no collective inside the loop touches an array
    of the head's element count, a chunk costs three products, and the
    result is the single device's."""
    kx, kw, kl = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, (MB, MS, MH))
    w = jax.random.normal(kw, (MH, MV)) * 0.1
    labels = jax.random.randint(kl, (MB, MS), 0, MV).at[:, -1].set(-100)

    def loss(x, w, labels):
        return fused_cross_entropy(x, w, labels, chunk_tokens=64, dense_fn=dense)

    vg = jax.value_and_grad(loss, argnums=(0, 1))
    v0, (dx0, dw0) = jax.jit(vg)(x, w, labels)

    sx = NamedSharding(fsdp4, P(("dp", "fsdp"), None, None))
    sw = NamedSharding(fsdp4, P(None, "fsdp") if head_dim == "vocabulary" else P("fsdp", None))
    sl = NamedSharding(fsdp4, P(("dp", "fsdp"), None))
    with attention_context(mesh=fsdp4):  # no mesh context: the program enters none
        sharded = jax.jit(vg, out_shardings=(None, (sx, sw)))
        args = (jax.device_put(x, sx), jax.device_put(w, sw), jax.device_put(labels, sl))
        text = sharded.lower(*args).compile().as_text()
        v, (dx, dw) = sharded(*args)

    np.testing.assert_allclose(v, v0, rtol=1e-6)
    np.testing.assert_allclose(dx, dx0, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dw, dw0, rtol=1e-5, atol=1e-7)
    assert dw.sharding.is_equivalent_to(sw, 2)

    bodies = loop_instructions(text)
    assert bodies, "the sweep compiled to no loop"
    for body, rows in bodies.items():
        assert sum(op in ("dot", "convolution") for _, op, _, _ in rows) <= 3, (body, rows)
        moved = [r for r in rows if _COLLECTIVE.match(r[1]) and r[2] >= MH * MV]
        assert not moved, (body, moved)


def test_a_tied_head_on_a_dp_fsdp_tp_mesh_equals_the_single_device():
    """Eight devices as ``dp=2, fsdp=2, tp=2`` and a tied ``[vocab, h]``
    head stored by the models' rule (vocabulary on ``tp``, hidden on
    ``fsdp``): the sweep spreads the vocabulary over ``fsdp x tp``, keeps
    the rows apart over ``dp``, and returns what one device returns."""
    sizes = {"dp": 2, "fsdp": 2, "tp": 2}
    mesh = Mesh(np.asarray(jax.devices()).reshape(tuple(sizes.get(ax, 1) for ax in MESH_AXIS_ORDER)),
                MESH_AXIS_ORDER)
    kx, kw, kl = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(kx, (MB, MS, MH))
    embed = jax.random.normal(kw, (MV, MH)) * 0.1
    labels = jax.random.randint(kl, (MB, MS), 0, MV).at[:, -1].set(-100)
    vg = jax.value_and_grad(
        lambda x, e, l: fused_cross_entropy(x, e, l, chunk_tokens=32, dense_fn=_tied),
        argnums=(0, 1))
    v0, (dx0, dw0) = jax.jit(vg)(x, embed, labels)
    sx = NamedSharding(mesh, P(("dp", "fsdp"), None, None))
    sw = NamedSharding(mesh, P("tp", "fsdp"))
    with attention_context(mesh=mesh):
        v, (dx, dw) = jax.jit(vg, out_shardings=(None, (sx, sw)))(
            jax.device_put(x, sx), jax.device_put(embed, sw),
            jax.device_put(labels, NamedSharding(mesh, P(("dp", "fsdp"), None))))
    np.testing.assert_allclose(v, v0, rtol=1e-6)
    np.testing.assert_allclose(dx, dx0, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dw, dw0, rtol=1e-5, atol=1e-7)


def test_chunks_are_counted_a_device(fsdp4):
    """``chunk_tokens`` rows of a chunk to ONE device: the same call cuts
    the sequence into a quarter as many chunks on four devices, and the
    float32 logits of a device's share stay under the cap at a large
    vocabulary."""
    from accelerate_tpu.ops import layers

    assert layers._ce_chunks(8, 4096, 32768, 1, 1024) == 32
    assert layers._ce_chunks(8, 4096, 32768, 4, 1024) == 8  # the train cell
    assert layers._ce_chunks(8, 4096, 32768, 4, 2048) == 4
    assert layers._ce_chunks(8, 4096, 151936, 4, 1024) == 32  # 256 MiB / (4 B x 151,936) = 441 rows
    assert layers._ce_chunks(2, 16, 64, 1, 1024) == 1
    assert layers._ce_chunks(2, 4098, 64, 1, 1024) == 683  # 4098 = 2 x 3 x 683: 6 positions, not 683
    assert layers._ce_layout(MV, (MH, MV)) == (1, None, None, None, None)
    with attention_context(mesh=fsdp4):
        n, *specs = layers._ce_layout(MV, (MH, MV))
        assert n == 4 and all(s.mesh == fsdp4 for s in specs)
        assert [s.spec for s in specs] == [
            P(None, None, None), P(None, None, ("fsdp",)), P(None, ("fsdp",)),
            P(("fsdp",), None, None)]
        assert layers._ce_layout(MV, (MV, MH))[3].spec == P(("fsdp",), None)  # a tied [vocab, h]
        assert layers._ce_layout(MV + 2, (MH, MV + 2))[1:] == (None,) * 4  # does not divide
