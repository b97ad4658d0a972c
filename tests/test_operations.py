"""Collectives vs closed-form expectations (reference analog:
``test_utils/scripts/test_ops.py`` — gather/broadcast/pad/reduce checks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu import operations as ops
from accelerate_tpu.mesh import data_sharding
from accelerate_tpu.state import PartialState


def _sharded_arange(state, n=16, width=2):
    x = jnp.arange(n * width, dtype=jnp.float32).reshape(n, width)
    return jax.device_put(x, data_sharding(state.mesh))


def test_gather_returns_global_view():
    state = PartialState()
    x = _sharded_arange(state)
    g = ops.gather(x)
    np.testing.assert_array_equal(np.asarray(g), np.arange(32, dtype=np.float32).reshape(16, 2))


def test_gather_pytree():
    state = PartialState()
    tree = {"a": _sharded_arange(state), "b": [jnp.ones((8,)), "keep"]}
    g = ops.gather(tree)
    assert g["b"][1] == "keep"
    assert np.asarray(g["a"]).shape == (16, 2)


def test_gather_object_single_process():
    assert ops.gather_object([1, "x"]) == [1, "x"]


def test_broadcast_identity_single_process():
    x = jnp.arange(4.0)
    np.testing.assert_array_equal(np.asarray(ops.broadcast(x)), np.arange(4.0))


def test_reduce_sum_over_shards():
    """A batch-sharded [16,2] over 8 dp shards reduces to [2,2]: the sum of
    the 8 per-shard tensors (the per-rank tensors of the torch contract)."""
    state = PartialState()
    x = _sharded_arange(state)  # [16, 2] split into 8 shards of [2, 2]
    out = ops.reduce(x, reduction="sum", scale=2.0)
    expected = np.asarray(x).reshape(8, 2, 2).sum(axis=0) * 2.0
    np.testing.assert_allclose(np.asarray(out), expected)
    mean_out = ops.reduce(x, reduction="mean")
    np.testing.assert_allclose(np.asarray(mean_out), expected / 16.0)


def test_reduce_replicated_identity():
    x = jnp.arange(6.0).reshape(3, 2)  # host value, single process
    out = ops.reduce(x, reduction="sum")
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_pad_across_processes_noop_single():
    x = jnp.ones((3, 5))
    out = ops.pad_across_processes(x, dim=1)
    assert np.asarray(out).shape == (3, 5)


def test_pad_input_tensors():
    batch = {"x": np.arange(10).reshape(5, 2), "n": 5}
    out = ops.pad_input_tensors(batch, batch_size=5, num_processes=4, dim=0)
    assert out["x"].shape == (8, 2)
    np.testing.assert_array_equal(out["x"][5], out["x"][4])
    np.testing.assert_array_equal(out["x"][7], out["x"][4])


def test_concatenate_nested():
    a = {"t": jnp.ones((2, 3))}
    b = {"t": jnp.zeros((4, 3))}
    out = ops.concatenate([a, b])
    assert out["t"].shape == (6, 3)


def test_convert_to_fp32():
    tree = {"a": jnp.ones((2,), dtype=jnp.bfloat16), "b": jnp.ones((2,), dtype=jnp.int32)}
    out = ops.convert_to_fp32(tree)
    assert out["a"].dtype == jnp.float32
    assert out["b"].dtype == jnp.int32


def test_listify_and_structure():
    tree = {"a": jnp.arange(3)}
    assert ops.listify(tree) == {"a": [0, 1, 2]}
    s = ops.get_data_structure(tree)
    assert s["a"].shape == (3,)


def test_send_to_device_sharding():
    state = PartialState()
    sharding = data_sharding(state.mesh)
    x = np.ones((16, 4), dtype=np.float32)
    y = ops.send_to_device({"x": x}, sharding)["x"]
    assert isinstance(y, jax.Array)
    assert y.sharding == sharding


def test_jops_psum_inside_shard_map():
    state = PartialState()
    mesh = state.mesh
    x = jax.device_put(
        jnp.arange(8.0).reshape(8, 1), NamedSharding(mesh, P(("dp",), None))
    )

    def body(x):
        return ops.jops.psum(x, "dp")

    out = jax.shard_map(
        body, mesh=mesh, in_specs=P(("dp",), None), out_specs=P(("dp",), None)
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 28.0))


def test_jops_ring_shift():
    state = PartialState()
    mesh = state.mesh
    x = jax.device_put(jnp.arange(8.0).reshape(8, 1), NamedSharding(mesh, P(("dp",), None)))

    def body(x):
        return ops.jops.ring_shift(x, "dp", shift=1)

    out = jax.shard_map(body, mesh=mesh, in_specs=P(("dp",), None), out_specs=P(("dp",), None))(x)
    # shard i receives shard i-1's value: [7, 0, 1, ..., 6]
    np.testing.assert_allclose(np.asarray(out).ravel(), np.r_[7.0, np.arange(7.0)])


def test_copy_tensor_to_devices_replicates():
    state = PartialState()
    x = jnp.arange(4.0)
    y = ops.copy_tensor_to_devices(x)
    assert y.sharding.is_fully_replicated


def test_find_batch_size_and_device():
    x = jnp.ones((5, 2))
    assert ops.find_batch_size({"a": [x], "b": 3}) == 5
    assert ops.find_device({"a": x}) is not None
