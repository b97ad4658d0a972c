"""Mamba-2 (state-space duality) pieces: the causal depthwise convolution
with a carried tail, the chunked scan from an incoming state, and the
decode-time state update as one named device operation.

One group of ``B`` / ``C`` is shared by every head (``mamba_n_groups`` 1,
the only layout the model zoo builds). Per head ``i``, with ``x_t`` of
``P`` lanes and ``B_t``, ``C_t`` of ``N``:

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t        [P, N]
    y_t = S_t C_t + D x_t

The recurrence runs in float32 whatever the compute dtype: its increments
are of relative size ``dt`` (about 1e-2) for thousands of steps, which a
bfloat16 state rounds away.

* :func:`conv_with_tail` — prefill chunk and decode step alike: the last
  ``d_conv - 1`` *valid* inputs of a row are its tail (bias and activation
  optional: LFM2's short convolution has neither).
* :func:`ssd_chunk_scan` — the chunked form (quadratic inside a chunk of
  ``chunk`` tokens, the recurrence between chunks) starting from any
  state; a token whose ``dt`` and ``x`` are zero leaves the state where it
  is, which is how a padded tail is kept out.
* :func:`ssm_state_update` — the ``s == 1`` step for every slot at once
  against the engine's stacked state ``[layers, slots, H, P, N]``: a Pallas
  kernel that addresses ``(layer, slot, head block)`` through its index
  maps and aliases the state in place (no layer's slab is sliced out or
  written back), with an ``interpret=True`` route for the CPU tests and a
  plain ``jnp`` twin (``impl="jnp"``) as the parity reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def default_ssm_impl() -> str:
    """The Pallas kernel on a TPU backend, the ``jnp`` twin elsewhere — a
    static choice by platform, like the paged-attention route."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def conv_with_tail(xbc, tail, weight, bias, n_valid, activation=jax.nn.silu):
    """Causal depthwise convolution of ``xbc [b, s, c]`` continuing from
    ``tail [b, k-1, c]`` (the inputs before the chunk), taps ``weight
    [k, c]`` (tap ``k-1`` multiplies the current token), ``bias [c]`` or
    ``None``. Returns ``(activation(conv) [b, s, c], new tail)``: the new
    tail is the last ``k-1`` inputs before position ``n_valid[b]`` — the
    last valid ones — so a row with no valid token keeps its tail bit for
    bit. Mamba-2 (``models/granite_hybrid.py``) adds a bias and applies
    ``silu``, the default; LFM2's gated short convolution
    (``models/lfm2.py``) has neither: ``bias=None, activation=None``."""
    k = weight.shape[0]
    s = xbc.shape[1]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)  # [b, k-1+s, c]
    acc = 0.0 if bias is None else bias.astype(jnp.float32)
    for j in range(k):
        acc = acc + full[:, j:j + s].astype(jnp.float32) * weight[j].astype(jnp.float32)
    out = (acc if activation is None else activation(acc)).astype(xbc.dtype)
    new_tail = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, k - 1, axis=0)
    )(full, jnp.asarray(n_valid, jnp.int32))
    return out, new_tail.astype(tail.dtype)


def ssd_chunk_scan(x, dt, a, b_mat, c_mat, state, chunk: int):
    """The chunked scan. ``x [b, s, H, P]``, ``dt [b, s, H]`` (after the
    softplus; zero on padding), ``a [H]`` (negative), ``b_mat`` / ``c_mat``
    ``[b, s, N]``, ``state [b, H, P, N]`` float32. Returns ``(y [b, s, H,
    P]`` float32 without the ``D`` skip, ``final state)``. ``s`` is padded
    up to a multiple of ``chunk`` with tokens that move nothing."""
    bsz, s, h, p = x.shape
    pad = (-s) % chunk
    if pad:
        x, dt, b_mat, c_mat = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b_mat, c_mat)
        )
    nc = (s + pad) // chunk
    f32 = jnp.float32

    def to_chunks(t):
        return jnp.moveaxis(t.astype(f32).reshape(bsz, nc, chunk, *t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one_chunk(st, inp):
        xc, dtc, bc, cc = inp                               # [b, L, ...]
        cs = jnp.cumsum(dtc * a.astype(f32), axis=1)        # [b, L, H], <= 0
        # inside the chunk: y_t += sum_{u<=t} exp(cs_t - cs_u) (C_t.B_u) dt_u x_u
        decay = jnp.exp(jnp.where(                          # [b, t, u, H]; 0 above the diagonal
            causal[None, :, :, None], cs[:, :, None, :] - cs[:, None, :, :], -jnp.inf))
        scores = jnp.einsum("btn,bun->btu", cc, bc, precision=_HI)
        w = scores[..., None] * decay * dtc[:, None, :, :]
        y = jnp.einsum("btuh,buhp->bthp", w, xc, precision=_HI)
        # what the incoming state adds: exp(cs_t) * (S C_t)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bhpn,btn->bthp", st, cc, precision=_HI)
        # the state the chunk leaves
        to_end = jnp.exp(cs[:, -1:, :] - cs) * dtc          # [b, u, H]
        st = jnp.exp(cs[:, -1])[..., None, None] * st + jnp.einsum(
            "buh,buhp,bun->bhpn", to_end, xc, bc, precision=_HI)
        return st, y

    state, ys = jax.lax.scan(
        one_chunk, state.astype(f32),
        (to_chunks(x), to_chunks(dt), to_chunks(b_mat), to_chunks(c_mat)),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, nc * chunk, h, p)[:, :s]
    return y, state


# ---------------------------------------------------------------------------
# the decode-time state update
# ---------------------------------------------------------------------------


def _state_update_jnp(state, layer, x, dt, a, b_vec, c_vec, active):
    """Plain twin of the kernel: slices layer ``layer`` out, steps it, and
    writes it back — what the kernel exists to avoid on the chip."""
    f32 = jnp.float32
    old = state[layer]                                       # [slots, H, P, N]
    decay = jnp.exp(dt.astype(f32) * a.astype(f32))          # [slots, H]
    inc = jnp.einsum("bh,bhp,bn->bhpn", dt.astype(f32), x.astype(f32),
                     b_vec.astype(f32), precision=_HI)
    new = (decay[..., None, None] * old.astype(f32) + inc).astype(state.dtype)
    y = jnp.einsum("bhpn,bn->bhp", new.astype(f32), c_vec.astype(f32), precision=_HI)
    on = active.reshape(-1).astype(bool)
    new = jnp.where(on[:, None, None, None], new, old)
    y = jnp.where(on[:, None, None], y, 0.0)
    return state.at[layer].set(new), y


def _state_update_kernel(layer_ref, active_ref, s_ref, decay_ref, dtx_ref,
                         b_ref, c_ref, so_ref, y_ref, *, hb, p):
    """Grid ``(slots, H // hb)``: step ``(i, j)`` holds slot ``i``'s head
    block ``j`` of the stacked state — ``[hb, P, N]``, steered there by the
    index maps from the prefetched layer. ``decay`` and ``dt * x`` arrive
    as ``[P, hb]`` columns, so that head ``k``'s scalar and vector are the
    lane slice ``[:, k:k+1]`` broadcast over the ``N`` lanes of the state.
    A slot that is not decoding copies its block through unchanged: the
    output aliases the input, and a block that is visited is written."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(active_ref[i] != 0)
    def _step():
        b_row = b_ref[...]                                   # [1, N]
        for k in range(hb):
            so_ref[k] = (s_ref[k].astype(jnp.float32) * decay_ref[:, k:k + 1]
                         + dtx_ref[:, k:k + 1] * b_row).astype(so_ref.dtype)
        flat = so_ref[...].astype(jnp.float32).reshape(hb * p, so_ref.shape[-1])
        y_ref[...] = jax.lax.dot_general(                    # [1, hb*P], contract N
            c_ref[...], flat, (((1,), (1,)), ((), ())),
            precision=_HI, preferred_element_type=jnp.float32,
        )

    @pl.when(active_ref[i] == 0)
    def _keep():
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


#: heads a grid step of the kernel takes (all of them where there are
#: fewer): at 64 x 128 float32 a head, 512 KB of state in and out
_HEAD_BLOCK = 16


def _state_update_pallas(state, layer, x, dt, a, b_vec, c_vec, active, *, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, slots, h, p, n = state.shape
    hb = min(_HEAD_BLOCK, h)
    if h % hb:
        raise ValueError(f"ssm_state_update: {h} heads do not split into blocks of {hb}")
    nblk = h // hb
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))                      # [slots, H]
    dtx = dt[..., None] * x.astype(f32)                      # [slots, H, P]

    def columns(t):                                          # [slots, H, P] -> [slots, nblk, P, hb]
        return t.reshape(slots, nblk, hb, p).transpose(0, 1, 3, 2)

    decay_cols = columns(jnp.broadcast_to(decay[..., None], (slots, h, p)))

    def block(i, j, ly, on):
        return (ly[0], i, j, 0, 0)

    def cols(i, j, ly, on):
        return (i, j, 0, 0)

    def row(i, j, ly, on):
        return (i, 0, 0)

    def out_row(i, j, ly, on):
        return (i, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # the layer steers the index maps; the mask the body
        grid=(slots, nblk),
        in_specs=[
            pl.BlockSpec((None, None, hb, p, n), block),
            pl.BlockSpec((None, None, p, hb), cols),
            pl.BlockSpec((None, None, p, hb), cols),
            pl.BlockSpec((None, 1, n), row),
            pl.BlockSpec((None, 1, n), row),
        ],
        out_specs=[
            pl.BlockSpec((None, None, hb, p, n), block),
            pl.BlockSpec((None, 1, hb * p), out_row),
        ],
    )
    new_state, y = pl.pallas_call(
        functools.partial(_state_update_kernel, hb=hb, p=p),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((slots, 1, h * p), f32),
        ],
        # operands count the two prefetched scalars: the state is the third
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="ssm_state_update",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        active.reshape(slots).astype(jnp.int32),
        state, decay_cols, columns(dtx),
        b_vec.astype(f32).reshape(slots, 1, n),
        c_vec.astype(f32).reshape(slots, 1, n),
    )
    return new_state, y.reshape(slots, h, p)


def ssm_state_update(state, layer, x, dt, a, b_vec, c_vec, active,
                     impl: str | None = None, interpret: bool = False):
    """One recurrence step of every slot in layer ``layer`` of the stacked
    state ``[layers, slots, H, P, N]`` (float32 as the model's spec has it,
    or what the engine's ``state_dtype`` says; the arithmetic is float32
    either way): ``x [slots, H, P]``, ``dt [slots, H]`` (after the softplus),
    ``a [H]``, ``b_vec`` / ``c_vec`` ``[slots, N]``, ``active [slots]``. Returns ``(state, y [slots, H, P]``
    float32 without the ``D`` skip``)``; a slot that is not active keeps
    its state bit for bit and reads ``y = 0``. ``layer`` may be traced."""
    if impl is None:
        impl = default_ssm_impl()
    layer = jnp.asarray(layer, jnp.int32)
    if impl == "jnp":
        return _state_update_jnp(state, layer, x, dt, a, b_vec, c_vec, active)
    if impl == "pallas":
        return _state_update_pallas(state, layer, x, dt, a, b_vec, c_vec, active,
                                    interpret=interpret)
    raise ValueError(f"unknown ssm_state_update impl {impl!r}")
