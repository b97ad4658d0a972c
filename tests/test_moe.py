"""The routed-expert operators of ``ops/moe.py`` (ISSUE 36): the sigmoid
router with a selection bias, the softmax router over all the experts
(ISSUE 38), and the dropless expert product, each held
against a token-by-token loop in numpy that shares nothing with them.

All on the CPU at small sizes. The expert product runs as
``jax.lax.ragged_dot`` there (the twin the platform picks) and, in these
tests, also as the grouped Pallas product in interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import moe

E, H, F, K, T = 8, 32, 16, 2, 12


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    return {
        "w_in": jax.random.normal(ks[0], (E, H, 2 * F)) / np.sqrt(H),
        "w_out": jax.random.normal(ks[1], (E, F, H)) / np.sqrt(F),
        "gate": jax.random.normal(ks[2], (H, E)),
        "x": jax.random.normal(ks[3], (T, H)),
    }


def _loop(x, experts, weights, w_in, w_out, live):
    """Every live token through each of its experts, one at a time."""
    x, w_in, w_out = (np.asarray(a, np.float64) for a in (x, w_in, w_out))
    out = np.zeros_like(x)
    for t in range(len(x)):
        for e, w in zip(np.asarray(experts[t]), np.asarray(weights[t], np.float64)):
            if live[t]:
                g, u = np.split(x[t] @ w_in[e], 2)
                out[t] += w * ((g / (1 + np.exp(-g)) * u) @ w_out[e])
    return out


def test_the_bias_moves_which_experts_are_picked_and_not_their_weights(layer):
    x, gate = layer["x"], layer["gate"]
    scores = np.asarray(jax.nn.sigmoid(x @ gate))
    plain, w_plain = moe.route(x, gate, jnp.zeros(E), K)
    # a bias that lifts expert 5 above every score: every token picks it
    bias = jnp.zeros(E).at[5].set(10.0)
    chosen, w = moe.route(x, gate, bias, K)
    assert (np.asarray(chosen) == 5).any(axis=1).all()
    assert not (np.asarray(plain) == 5).any(axis=1).all()
    # the weights are the un-biased scores of the experts chosen, normalised
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=1)
    np.testing.assert_allclose(w, picked / (picked.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    # and with no bias they are what the plain top-k of the scores gives
    np.testing.assert_array_equal(np.asarray(plain), np.argsort(-scores, axis=1)[:, :K])
    assert float(jnp.abs(w_plain.sum(1) - 1).max()) < 1e-5


@pytest.mark.parametrize("norm, scale", [(True, 1.0), (True, 2.5), (False, 1.0), (False, 0.5)])
def test_the_sum_s_epsilon_and_the_scaling_factor(layer, norm, scale):
    x, gate = layer["x"], layer["gate"]
    chosen, w = moe.route(x, gate, jnp.zeros(E), K, norm, scale)
    picked = np.take_along_axis(np.asarray(jax.nn.sigmoid(x @ gate)), np.asarray(chosen), axis=1)
    want = picked / (picked.sum(1, keepdims=True) + 1e-6) if norm else picked
    np.testing.assert_allclose(w, want * scale, rtol=1e-6)
    if norm:
        # the 1e-6 is there: the weights sum to just under the factor
        assert (np.asarray(w).sum(1) < scale).all()


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("k", [1, 2, 8])
def test_the_softmax_router_scores_over_all_the_experts(layer, norm, k):
    """``scoring="softmax"``: one softmax over all E experts, the top k of
    it, the chosen weights renormalised (or not), no bias where the model
    has none; against numpy."""
    x, gate = layer["x"], layer["gate"]
    logits = np.asarray(x, np.float64) @ np.asarray(gate, np.float64)
    scores = np.exp(logits - logits.max(1, keepdims=True))
    scores /= scores.sum(1, keepdims=True)
    chosen, w = moe.route(x, gate, None, k, norm, scoring="softmax")
    np.testing.assert_array_equal(np.asarray(chosen), np.argsort(-scores, axis=1)[:, :k])
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=1)
    want = picked / (picked.sum(1, keepdims=True) + 1e-6) if norm else picked
    np.testing.assert_allclose(w, want, rtol=2e-5)
    assert w.dtype == jnp.float32 and chosen.dtype == jnp.int32
    if k == E and not norm:
        np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, rtol=1e-5)


def test_the_two_scorings_differ_and_an_unknown_one_is_refused(layer):
    x, gate = layer["x"], layer["gate"]
    _, w_sig = moe.route(x, gate, None, K, False)
    _, w_soft = moe.route(x, gate, None, K, False, scoring="softmax")
    assert float(jnp.abs(w_sig - w_soft).max()) > 1e-2
    # without a bias the sigmoid router is what a zero bias gives
    for got, want in zip(moe.route(x, gate, None, K), moe.route(x, gate, jnp.zeros(E), K)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="unknown router scoring 'tanh'"):
        moe.route(x, gate, None, K, scoring="tanh")


def test_the_softmax_router_runs_in_float32_whatever_it_is_given(layer):
    x, gate = layer["x"].astype(jnp.bfloat16), layer["gate"].astype(jnp.bfloat16)
    chosen, w = moe.route(x, gate, None, K, scoring="softmax")
    want, w_want = moe.route(x.astype(jnp.float32), gate.astype(jnp.float32), None, K,
                             scoring="softmax")
    assert w.dtype == jnp.float32
    np.testing.assert_array_equal(chosen, want)
    np.testing.assert_allclose(w, w_want, rtol=1e-6)


def test_the_router_runs_in_float32_whatever_it_is_given(layer):
    """bfloat16 inputs are multiplied as float32: two scores that tie in
    bfloat16 are told apart."""
    x, gate = layer["x"].astype(jnp.bfloat16), layer["gate"].astype(jnp.bfloat16)
    chosen, w = moe.route(x, gate, jnp.zeros(E, jnp.bfloat16), K)
    want, w_want = moe.route(x.astype(jnp.float32), gate.astype(jnp.float32), jnp.zeros(E), K)
    assert w.dtype == jnp.float32
    np.testing.assert_array_equal(chosen, want)
    np.testing.assert_allclose(w, w_want, rtol=1e-6)


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_the_expert_product_agrees_with_a_token_loop(layer, impl):
    x = layer["x"]
    experts, weights = moe.route(x, layer["gate"], jnp.zeros(E), K)
    live = np.ones(T, bool)
    y, counts = moe.expert_ffn(x, experts, weights, layer["w_in"], layer["w_out"],
                               impl=impl, interpret=True)
    np.testing.assert_allclose(
        y, _loop(x, experts, weights, layer["w_in"], layer["w_out"], live), atol=2e-6)
    np.testing.assert_array_equal(counts, np.bincount(np.asarray(experts).ravel(), minlength=E))


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_dropless_under_imbalance_every_token_to_one_expert(layer, impl):
    """A router forced to one expert and its neighbour: expert 3 is given
    every token. A capacity-bounded layer (``models/mixtral.py:moe_ffn``,
    capacity 2 * T * k / E = 6 rows here) keeps 6 of the 12 and drops the
    rest; this one multiplies all 12."""
    x = layer["x"]
    experts = jnp.tile(jnp.asarray([[3, 4]], jnp.int32), (T, 1))
    weights = jnp.tile(jnp.asarray([[0.75, 0.25]], jnp.float32), (T, 1))
    y, counts = moe.expert_ffn(x, experts, weights, layer["w_in"], layer["w_out"],
                               impl=impl, interpret=True)
    np.testing.assert_array_equal(counts, [0, 0, 0, T, T, 0, 0, 0])
    want = _loop(x, experts, weights, layer["w_in"], layer["w_out"], np.ones(T, bool))
    np.testing.assert_allclose(y, want, atol=2e-6)
    assert np.abs(want).min(axis=1).max() > 0  # no token's row is empty


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_a_dead_lane_adds_no_pair_to_any_expert(layer, impl):
    x = layer["x"]
    experts, weights = moe.route(x, layer["gate"], jnp.zeros(E), K)
    live = np.arange(T) % 3 != 1
    y, counts = moe.expert_ffn(x, experts, weights, layer["w_in"], layer["w_out"],
                               live=jnp.asarray(live), impl=impl, interpret=True)
    assert int(counts.sum()) == live.sum() * K
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(experts)[live].ravel(), minlength=E))
    assert not np.asarray(y)[~live].any()
    np.testing.assert_allclose(
        y, _loop(x, experts, weights, layer["w_in"], layer["w_out"], live), atol=2e-6)
    # nobody live: no pair, no output, whatever the implementation leaves
    # in the rows it did not compute
    y, counts = moe.expert_ffn(x, experts, weights, layer["w_in"], layer["w_out"],
                               live=jnp.zeros(T, bool), impl=impl, interpret=True)
    assert int(counts.sum()) == 0 and not np.asarray(y).any()


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_a_layer_of_the_stacked_matrices_is_addressed_in_place(layer, impl):
    """``layer=i`` over ``[layers, E, ...]`` is the product with layer
    ``i``'s experts, and no other layer's."""
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    w_in = jax.random.normal(ks[0], (3, E, H, 2 * F)) / np.sqrt(H)
    w_out = jax.random.normal(ks[1], (3, E, F, H)) / np.sqrt(F)
    x = layer["x"]
    experts, weights = moe.route(x, layer["gate"], jnp.zeros(E), K)
    for i in range(3):
        y, counts = moe.expert_ffn(x, experts, weights, w_in, w_out, layer=i,
                                   impl=impl, interpret=True)
        want, want_counts = moe.expert_ffn(x, experts, weights, w_in[i], w_out[i], impl="ragged")
        np.testing.assert_allclose(y, want, atol=2e-6)
        np.testing.assert_array_equal(counts, want_counts)


def test_shapes_are_static_and_the_product_can_be_differentiated(layer):
    x = layer["x"]

    def loss(w_in, w_out, gate):
        experts, weights = moe.route(x, gate, jnp.zeros(E), K)
        y, _ = moe.expert_ffn(x, experts, weights, w_in, w_out)
        return (y ** 2).sum()

    jitted = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    grads = jitted(layer["w_in"], layer["w_out"], layer["gate"])
    assert all(np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).max()) > 0 for g in grads)
    # another routing, the same executable
    jitted(layer["w_in"], layer["w_out"], -layer["gate"])
    assert jitted._cache_size() == 1


def test_the_platform_picks_the_implementation():
    assert moe.default_moe_impl() == "ragged"  # the CPU's; "gmm" on a TPU backend
    with pytest.raises(ValueError, match="unknown grouped_matmul impl"):
        moe.grouped_matmul(jnp.zeros((4, 8)), jnp.zeros((2, 8, 8)), jnp.asarray([2, 2]), "dense")


# -- a chip's share of the experts, and the grouped choice (ISSUE 42) ---------------


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
@pytest.mark.parametrize("first", [0, 2, 5])
def test_a_held_range_computes_its_own_experts_part_and_leaves_the_rest_out(layer, impl, first):
    """Experts ``first .. first + 2`` of the 8 are held: the router chose
    among all 8, the pairs of the other experts take the dead lane, the
    counts are over the 3 held, and the sum is the token loop's over the
    pairs whose expert is held."""
    x = layer["x"]
    experts, weights = moe.route(x, layer["gate"], jnp.zeros(E), 4)
    live = np.arange(T) % 4 != 2
    held = slice(first, first + 3)
    y, counts = moe.expert_ffn(
        x, experts, weights, layer["w_in"][held], layer["w_out"][held],
        live=jnp.asarray(live), impl=impl, interpret=True, held=(first, 3))
    e = np.asarray(experts)
    here = (e >= first) & (e < first + 3)
    np.testing.assert_array_equal(
        counts, np.bincount(e[live][here[live]] - first, minlength=3))
    want = _loop(x, e, np.where(here, np.asarray(weights), 0.0), layer["w_in"],
                 layer["w_out"], live)
    np.testing.assert_allclose(y, want, atol=2e-6)
    # the pairs routed elsewhere are the live pairs less those counted
    assert live.sum() * 4 - int(counts.sum()) == (~here)[live].sum()
    # every range's part adds up to the whole layer's
    if first == 0:
        whole, _ = moe.expert_ffn(x, experts, weights, layer["w_in"], layer["w_out"],
                                  live=jnp.asarray(live), impl=impl, interpret=True)
        parts = [moe.expert_ffn(x, experts, weights, layer["w_in"][a:a + 4],
                                layer["w_out"][a:a + 4], live=jnp.asarray(live), impl=impl,
                                interpret=True, held=(a, 4))[0] for a in (0, 4)]
        np.testing.assert_allclose(parts[0] + parts[1], whole, atol=2e-6)


def test_a_held_range_of_the_stacked_matrices_and_a_wrong_count(layer):
    x = layer["x"]
    experts, weights = moe.route(x, layer["gate"], jnp.zeros(E), K)
    stack_in = jnp.stack([layer["w_in"][4:] * 0, layer["w_in"][4:]])
    stack_out = jnp.stack([layer["w_out"][4:] * 0, layer["w_out"][4:]])
    y, counts = moe.expert_ffn(x, experts, weights, stack_in, stack_out, layer=1, held=(4, 4))
    alone, alone_counts = moe.expert_ffn(x, experts, weights, layer["w_in"][4:],
                                         layer["w_out"][4:], held=(4, 4))
    np.testing.assert_allclose(y, alone, atol=1e-6)
    np.testing.assert_array_equal(counts, alone_counts)
    with pytest.raises(ValueError, match=r"held \(4, 3\): the matrices are of 4 experts"):
        moe.expert_ffn(x, experts, weights, layer["w_in"][4:], layer["w_out"][4:], held=(4, 3))


def test_the_groups_limit_the_choice_and_a_dropped_groups_score_counts_as_zero(layer):
    """8 experts in 4 groups of 2, the best 2 groups kept, top 3: every
    chosen expert lies in a kept group (a group's mark is the sum of its two
    biased scores), and the third choice of a token comes from the kept
    groups even where a dropped group's expert scores higher."""
    x, gate = layer["x"], layer["gate"]
    bias = jnp.asarray(np.linspace(-0.2, 0.2, E), jnp.float32)
    experts, weights = moe.route(x, gate, bias, 3, True, 1.0, n_group=4, topk_group=2)
    scores = np.asarray(jax.nn.sigmoid(x @ gate))
    biased = scores + np.asarray(bias)
    marks = biased.reshape(T, 4, 2).sum(-1)
    kept = np.argsort(-marks, axis=1)[:, :2]
    e = np.asarray(experts)
    assert all(set(row // 2) <= set(k) for row, k in zip(e, kept))
    free, _ = moe.route(x, gate, bias, 3, True, 1.0)
    assert not np.array_equal(np.asarray(free), e)  # somewhere a dropped group scored higher
    picked = np.take_along_axis(scores, e, axis=1)
    np.testing.assert_allclose(weights, picked / (picked.sum(1, keepdims=True) + 1e-6),
                               rtol=1e-6)


#: sha256 of the jaxpr of both routers and the expert product (flat and
#: stacked) at their defaults, taken on the parent commit (6756b7a): the
#: groups, ``norm_eps`` and ``held`` are static branches, and at ``n_group`` 1
#: without ``held`` the operators trace what they traced before they were there
PARENT_JAXPR = "9879b33ff22be777c9becb025c8abd349753a533a47862f4beae887a06137962"


_JAXPR_SCRIPT = """
import hashlib
import jax, jax.numpy as jnp
from accelerate_tpu.ops import moe
x, gate, bias = jnp.zeros((12, 32)), jnp.zeros((32, 8)), jnp.zeros((8,))
w_in, w_out = jnp.zeros((8, 32, 32)), jnp.zeros((8, 16, 32))
def program(x, gate, bias, w_in, w_out, live):
    e, w = moe.route(x, gate, bias, 2, True, 2.5)
    e2, w2 = moe.route(x, gate, None, 2, True, 1.0, scoring="softmax")
    y, c = moe.expert_ffn(x, e, w, w_in, w_out, live=live, impl="ragged")
    y2, c2 = moe.expert_ffn(x, e2, w2, w_in[None], w_out[None], layer=0, impl="ragged")
    return y, c, y2, c2
text = str(jax.make_jaxpr(program)(x, gate, bias, w_in, w_out, jnp.ones((12,), bool)))
print("DIGEST " + hashlib.sha256(text.encode()).hexdigest())
"""


def test_at_one_group_and_nothing_held_the_operators_trace_the_parents_program():
    """In a process of its own, as ``tests/test_lfm2.py``'s digests: what a
    program traces to also depends on process-wide settings other tests
    change, and the digest was taken in a fresh one."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", _JAXPR_SCRIPT], cwd=root, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    assert done.returncode == 0, done.stderr[-2000:]
    got = next(l for l in done.stdout.splitlines() if l.startswith("DIGEST "))[7:]
    assert got == PARENT_JAXPR


def test_a_contraction_the_tile_does_not_divide_takes_the_tile_halved(monkeypatch):
    """``k`` = 384 under a tile of 256: the grouped product runs at 128 (no
    masked part tile) and agrees with ``ragged_dot``; a ``k`` the tile divides
    keeps it."""
    seen = []
    import jax.experimental.pallas.ops.tpu.megablox as megablox

    real = megablox.gmm

    def spy(lhs, rhs, sizes, dtype, tiling, *rest):
        seen.append(tiling)
        return real(lhs, rhs, sizes, dtype, tiling, *rest)

    monkeypatch.setattr(moe, "_GMM_TILING", (128, 256, 128))
    monkeypatch.setattr(megablox, "gmm", spy)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    sizes = jnp.asarray([100, 0, 156], jnp.int32)
    for k in (384, 512):
        lhs = jax.random.normal(ks[0], (256, k))
        rhs = jax.random.normal(ks[1], (3, k, 128)) / np.sqrt(k)
        got = moe.grouped_matmul(lhs, rhs, sizes, impl="gmm", interpret=True)
        np.testing.assert_allclose(got, moe.grouped_matmul(lhs, rhs, sizes, impl="ragged"),
                                   atol=2e-5)
    assert [t[1] for t in seen] == [128, 256]
