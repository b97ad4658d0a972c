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
* :func:`ssm_state_update` — the ``s == 1`` step for every live slot at
  once against the engine's stacked state ``[layers, slots, H, P, N]``: a
  Pallas kernel that walks the list of live slots (:func:`live_slots`, read
  from a prefetched operand), copies block ``(layer, slot, head block)``
  from HBM one block ahead of the one it steps and back in place while the
  next is stepped (the state is aliased: no layer's slab is sliced out or
  written back, and a dead slot is neither read nor written, so a call
  costs what is live), with an ``interpret=True`` route for the CPU tests
  and a plain ``jnp`` twin (``impl="jnp"``) as the parity reference.
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


def live_slots(active):
    """What the kernel's walk follows, from ``active [slots]``: ``(the live
    slots' indices in slot order [slots] int32 — the entries past the live
    ones are 0 and never read —, how many are live [1] int32)``. A dozen
    small operations on the device; a caller that runs many layers against
    one mask derives it once and hands it to every call (``live=``)."""
    on = jnp.asarray(active).reshape(-1) != 0
    (idx,) = jnp.nonzero(on, size=on.shape[0], fill_value=0)
    return idx.astype(jnp.int32), on.sum(dtype=jnp.int32).reshape(1)


#: head blocks on their way from HBM while the kernel steps one (so
#: ``_AHEAD + 1`` VMEM buffers in, and two out: block ``k - 1`` is copied
#: out while block ``k`` is stepped). Chosen on the v5e, where 2 and 3 ahead
#: read within 1.5 % of 1 (PERF.md section 6, PR 37); a constant of the kernel
_AHEAD = 1


def _state_update_kernel(layer_ref, live_ref, n_live_ref, s_hbm, cols_ref, b_ref, c_ref,
                         so_hbm, y_ref, s_buf, so_buf, sems, *, hb, p, nblk):
    """No grid: one loop over the ``nblk`` head blocks of each of the
    ``n_live_ref[0]`` live slots ``live_ref[0], live_ref[1], ...`` (slot
    order). The stacked state stays in HBM, whole; the kernel copies block
    ``(layer, slot, head block)`` — ``[hb, P, N]`` — into one of its VMEM
    buffers itself, ``_AHEAD`` blocks ahead of the one it steps, and the
    stepped block back to the same place (the output aliases the input)
    while the next is stepped. A slot that is not live is never read and
    never written; its ``y`` row is the zero the output starts as.

    ``cols_ref [slots, P, L]`` holds, lane-dense, what a head's step
    broadcasts over the ``N`` lanes of its state: lane ``k`` of slot ``i``
    is head ``k``'s decay (every row the same) and lane ``H + k`` its
    ``dt * x`` column. A lane rotation brings a block's heads to static
    offsets, so the body is traced once, not once a head block."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layer, total = layer_ref[0], n_live_ref[0] * nblk
    h, lanes = nblk * hb, cols_ref.shape[-1]
    depth = _AHEAD + 1

    def block(ref, t):
        """Block ``t`` of the walk in ``ref``: head block ``t % nblk`` of
        the ``t // nblk``-th live slot."""
        return ref.at[layer, live_ref[t // nblk], pl.ds(t % nblk * hb, hb)]

    def copy_in(t):
        return pltpu.make_async_copy(block(s_hbm, t), s_buf.at[t % depth], sems.at[0, t % depth])

    def copy_out(t):
        return pltpu.make_async_copy(so_buf.at[t % 2], block(so_hbm, t), sems.at[1, t % 2])

    y_ref[...] = jnp.zeros_like(y_ref)
    for t in range(_AHEAD):
        @pl.when(t < total)
        def _first():
            copy_in(t).start()

    def _step(t, carry):
        @pl.when(t + _AHEAD < total)
        def _ahead():
            copy_in(t + _AHEAD).start()

        @pl.when(t >= 2)
        def _buffer_free():
            copy_out(t - 2).wait()

        copy_in(t).wait()
        slot, j = live_ref[t // nblk], t % nblk
        s_ref, so_ref = s_buf.at[t % depth], so_buf.at[t % 2]
        b_row = b_ref[slot]                                  # [1, N]
        # this block's heads to lanes 0 .. hb-1 (decay) and H .. H+hb-1 (dt * x)
        cols = pltpu.roll(cols_ref[slot], (lanes - j * hb) % lanes, axis=1)
        for k in range(hb):
            so_ref[k] = (s_ref[k].astype(jnp.float32) * cols[:, k:k + 1]
                         + cols[:, h + k:h + k + 1] * b_row).astype(so_ref.dtype)
        flat = so_ref[...].astype(jnp.float32).reshape(hb * p, so_ref.shape[-1])
        y_ref.at[slot][:, pl.ds(pl.multiple_of(j * (hb * p), hb * p), hb * p)] = (
            jax.lax.dot_general(                             # [1, hb*P], contract N
                c_ref[slot], flat, (((1,), (1,)), ((), ())),
                precision=_HI, preferred_element_type=jnp.float32,
            ))
        copy_out(t).start()
        return carry

    jax.lax.fori_loop(0, total, _step, 0)
    # the last two blocks' copies out are still on their way
    for back in (2, 1):
        @pl.when(total >= back)
        def _drain():
            copy_out(total - back).wait()


#: heads a block of the walk takes (all of them where there are fewer): at
#: 64 x 128 float32 a head, 512 KB of state in and out
_HEAD_BLOCK = 16


# a program calls this once for every run of state layers (five scan bodies in
# Granite-4.0-H): under ``jit`` the kernel is traced and lowered to Mosaic
# once for them all, which is seconds of a server's set-up on a small host
@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_update_pallas(state, layer, x, dt, a, b_vec, c_vec, active, live, *, interpret):
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
    # [slots, P, decay's H lanes + dt*x's H lanes], padded to whole vregs
    cols = jnp.concatenate(
        [jnp.broadcast_to(decay[:, None, :], (slots, p, h)), dtx.transpose(0, 2, 1)], axis=-1)
    cols = jnp.pad(cols, [(0, 0), (0, 0), (0, -2 * h % 128)])
    idx, n_live = live_slots(active) if live is None else live

    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the layer and the live list steer the walk
        grid=(),
        in_specs=[in_hbm, whole, whole, whole],
        out_specs=[in_hbm, whole],
        scratch_shapes=[
            pltpu.VMEM((_AHEAD + 1, hb, p, n), state.dtype),
            pltpu.VMEM((2, hb, p, n), state.dtype),
            pltpu.SemaphoreType.DMA((2, max(_AHEAD + 1, 2))),
        ],
    )
    new_state, y = pl.pallas_call(
        functools.partial(_state_update_kernel, hb=hb, p=p, nblk=nblk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            # ``[slots, 1, H*P]`` and ``B`` / ``C`` as ``[slots, 1, N]``: XLA lays
            # the step's neighbouring fusions out from the call's operand
            # shapes, and a row a slot keeps them (and the order of their
            # float32 sums) what they were under the grid over slots
            jax.ShapeDtypeStruct((slots, 1, h * p), f32),
        ],
        # operands count the three prefetched scalars: the state is the fourth
        input_output_aliases={3: 0},
        interpret=interpret,
        name="ssm_state_update",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(idx, jnp.int32).reshape(slots),
        jnp.asarray(n_live, jnp.int32).reshape(1),
        state, cols,
        b_vec.astype(f32).reshape(slots, 1, n),
        c_vec.astype(f32).reshape(slots, 1, n),
    )
    return new_state, y.reshape(slots, h, p)


def ssm_state_update(state, layer, x, dt, a, b_vec, c_vec, active,
                     impl: str | None = None, interpret: bool = False, live=None):
    """One recurrence step of every live slot in layer ``layer`` of the stacked
    state ``[layers, slots, H, P, N]`` (float32 as the model's spec has it,
    or what the engine's ``state_dtype`` says; the arithmetic is float32
    either way): ``x [slots, H, P]``, ``dt [slots, H]`` (after the softplus),
    ``a [H]``, ``b_vec`` / ``c_vec`` ``[slots, N]``, ``active [slots]``. Returns ``(state, y [slots, H, P]``
    float32 without the ``D`` skip``)``; a slot that is not active keeps
    its state bit for bit and reads ``y = 0`` whatever its operands hold: the
    kernel's work follows the live slots. ``live`` is :func:`live_slots` of
    ``active`` where the caller has it already (one mask, many layers);
    left out, it is derived here. ``layer`` may be traced."""
    if impl is None:
        impl = default_ssm_impl()
    layer = jnp.asarray(layer, jnp.int32)
    if impl == "jnp":
        return _state_update_jnp(state, layer, x, dt, a, b_vec, c_vec, active)
    if impl == "pallas":
        return _state_update_pallas(state, layer, x, dt, a, b_vec, c_vec, active, live,
                                    interpret=interpret)
    raise ValueError(f"unknown ssm_state_update impl {impl!r}")
