"""Fused paged attention + quantized KV storage (``ops/paged_attention.py``,
the ``ops/fp8.py`` KV quantize helpers, and the quantizing
``write_paged_kv``).

All ops-level and tier-1: tiny shapes, CPU-cheap. The parity contract is
layered — the fused lax walk must match the gather-then-dense reference to
f32 noise at float storage, and the quantized paths must match the f32
reference within the documented per-dtype tolerances (these same numbers
gate the engine-level matrix in ``tests/test_serving.py`` and are quoted in
``docs/source/usage_guides/serving.md``).
"""

import importlib

import numpy as np
import pytest

import jax.numpy as jnp

from accelerate_tpu.ops.fp8 import (
    dequantize_kv,
    kv_qmax,
    kv_storage_dtype,
    quantize_kv_rows,
)
from accelerate_tpu.ops.layers import cached_attention, last_visible, write_paged_kv
from accelerate_tpu.ops.paged_attention import (
    _CHUNK_TILE,
    _LATENT_ROW_BLOCK,
    _ROW_BLOCK,
    _TILE,
    _step_geometry,
    latent_attention,
    paged_attention,
    tile_entries,
)

#: the module (the package exports the function under the same name)
paged_module = importlib.import_module("accelerate_tpu.ops.paged_attention")

#: ops-level |fused_quantized - f32_reference| ceilings on attention
#: outputs (unit-variance inputs). int8 carries ~0.4% relative error per
#: row (7-bit mantissa + rounding), fp8 e4m3 ~3% (3-bit mantissa).
KV_ATOL = {"int8": 0.05, "fp8": 0.12}


#: the stacked pools of these tests hold three layers; the layer under
#: test is addressed by index and the others are filled with noise, so a
#: read or a write that strays into another layer's rows changes a result
LAYERS = 3


def _noise_pool(rng, shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(-100, 100, size=shape), dtype)
    return jnp.asarray(rng.normal(size=shape) * 4.0, jnp.float32).astype(dtype)


def _filled_pools(rng, *, b=3, n_kv=4, hd=16, bs=4, nb=12, mb=5, idx=(9, 6, 14),
                  dtype=None, layer=0):
    """Stacked pools (``[LAYERS, nb, bs, n_kv*hd]``) whose layer ``layer``
    is written position-by-position through real block tables: the f32
    pools are ground truth; quantized pools (dtype given) are written
    through the same scatter with scale arrays. Every other layer holds
    noise, and must come out of the writes as it went in."""
    bt = np.zeros((b, mb), np.int32)
    used = iter(range(1, nb))
    for i, ix in enumerate(idx):
        for j in range((ix // bs) + 1):
            bt[i, j] = next(used)
    idx = np.asarray(idx, np.int32)
    shape = (LAYERS, nb, bs, n_kv * hd)
    kpf = _noise_pool(rng, shape, jnp.float32).at[layer].set(0.0)
    vpf = _noise_pool(rng, shape, jnp.float32).at[layer].set(0.0)
    before = [np.asarray(kpf), np.asarray(vpf)]
    q_pools = None
    if dtype is not None:
        kp = _noise_pool(rng, shape, dtype).at[layer].set(0)
        vp = _noise_pool(rng, shape, dtype).at[layer].set(0)
        ks = jnp.asarray(rng.random(shape[:-1] + (n_kv,)) + 0.5, jnp.float32)
        ks = ks.at[layer].set(1.0)
        vs = ks + 0.25
        vs = vs.at[layer].set(1.0)
        q_pools = (kp, vp, ks, vs)
        before += [np.asarray(x) for x in q_pools]
    for p in range(int(idx.max()) + 1):
        k = jnp.asarray(rng.normal(size=(b, 1, n_kv, hd)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, 1, n_kv, hd)).astype(np.float32))
        mask = np.asarray([[p <= ix] for ix in idx])
        pos = np.full((b, 1), p, np.int32)
        kpf, vpf = write_paged_kv(kpf, vpf, layer, k, v, bt, pos, write_mask=mask)
        if q_pools is not None:
            q_pools = write_paged_kv(
                *q_pools[:2], layer, k, v, bt, pos, write_mask=mask,
                k_scale=q_pools[2], v_scale=q_pools[3],
            )
    others = [i for i in range(LAYERS) if i != layer]
    for was, now in zip(before, (kpf, vpf, *(q_pools or ()))):
        np.testing.assert_array_equal(np.asarray(now)[others], was[others])
    return bt, idx, (kpf, vpf), q_pools


def _dense_reference(q, kpf, vpf, layer, bt, qi, n_kv):
    """The span of layer ``layer`` gathered in numpy (no code of the paged
    routes), through :func:`cached_attention`."""
    kh, vh = np.asarray(kpf)[layer], np.asarray(vpf)[layer]
    nb, bs, width = kh.shape
    b, mb = bt.shape
    span = lambda pool: pool[bt].reshape(b, mb * bs, n_kv, width // n_kv)
    return cached_attention(q, jnp.asarray(span(kh)), jnp.asarray(span(vh)), qi)


@pytest.mark.parametrize("layer", [0, 2])
def test_fused_lax_matches_gather_reference(layer):
    """The scan-over-blocks online softmax equals the PR 4
    gather-then-``cached_attention`` path to f32 noise — decode (s=1) and
    prefill-chunk (s>1) query shapes, GQA heads — on the stacked pool at
    the layer it is asked for, and the gather route equals a span taken
    out of that layer by hand."""
    rng = np.random.default_rng(0)
    bt, idx, (kpf, vpf), _ = _filled_pools(rng, layer=layer)
    for s, offs in ((1, 0), (4, 3)):
        q = jnp.asarray(rng.normal(size=(3, s, 8, 16)).astype(np.float32))
        qi = np.maximum(idx - offs, 0)
        ref = paged_attention(q, kpf, vpf, layer, bt, qi, impl="gather")
        fused = paged_attention(q, kpf, vpf, layer, bt, qi, impl="lax")
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(_dense_reference(q, kpf, vpf, layer, bt, qi, 4)),
            rtol=1e-6, atol=1e-6,
        )


@pytest.mark.parametrize("layer", [0, 1])
def test_pallas_kernel_matches_gather_reference(layer):
    """The Pallas block-table kernel (in the Pallas interpreter off-TPU)
    computes the same attention as the gather reference — decode and
    prefill-chunk query shapes, GQA heads — reading layer ``layer`` of the
    stacked pool through its index map."""
    rng = np.random.default_rng(1)
    bt, idx, (kpf, vpf), _ = _filled_pools(rng, layer=layer)
    for s, offs in ((1, 0), (4, 3)):
        q = jnp.asarray(rng.normal(size=(3, s, 8, 16)).astype(np.float32))
        qi = np.maximum(idx - offs, 0)
        ref = paged_attention(q, kpf, vpf, layer, bt, qi, impl="gather")
        out = paged_attention(q, kpf, vpf, layer, bt, qi, impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


#: the row walk's geometry: a table of four entries of sixteen tokens, so a
#: row's context ends inside the first block, on its last row, on the
#: first row of the next, or fills the table; ``None`` is a free slot as the
#: engine dispatches it (position 0, every entry the null block)
RAGGED = (1, 15, 16, 17, 64, None)
WALK_BS, WALK_MB = 16, 4


def _ragged_case(rng, contexts, s, hd, store, tail, mb=WALK_MB, rep=2):
    """Random stacked pools (two kv heads, GQA x ``rep``) and one block table
    of ``mb`` entries a row: a row whose last query sits at position
    ``context - 1`` holds blocks for entries ``0 .. (context - 1) // 16``.
    Every later entry — which no query of the row attends — points at
    ``tail``: the null block 0, or block 1, which is filled with NaN (its
    scales, for an int8 pool)."""
    n_kv, nh, nb = 2, 2 * rep, 2 + len(contexts) * mb
    shape = (LAYERS, nb, WALK_BS, n_kv * hd)
    scales = []
    if store in ("int8", "fp8"):
        if store == "int8":
            pools = [jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8) for _ in range(2)]
        else:
            pools = [jnp.asarray(rng.normal(size=shape) * 60.0, jnp.float32)
                     .astype(jnp.float8_e4m3fn) for _ in range(2)]
        scales = [jnp.asarray(rng.random(shape[:-1] + (n_kv,)) * 0.02 + 0.005, jnp.float32)
                  for _ in range(2)]
        scales = [x.at[:, 1].set(jnp.nan) for x in scales]
    else:
        pools = [jnp.asarray(rng.normal(size=shape), jnp.float32).at[:, 1].set(jnp.nan)
                 for _ in range(2)]
    bt = np.full((len(contexts), mb), tail, np.int32)
    idx = np.zeros((len(contexts),), np.int32)
    used = iter(range(2, nb))
    for i, context in enumerate(contexts):
        if context is None:
            bt[i, 0] = 0        # the walk visits entry 0 of a free slot
            continue
        idx[i] = context - s
        for j in range((context - 1) // WALK_BS + 1):
            bt[i, j] = next(used)
    q = jnp.asarray(rng.normal(size=(len(contexts), s, nh, hd)), jnp.float32)
    return q, pools, bt, idx, scales


def _walk_shapes():
    """(b 8, s 1): the ragged rows of one decode call, a free slot among
    them; (b 1, s 32): a chunk whose last query ends each ragged context
    that a chunk of 32 can end (a first chunk: 32; a later one: 33, 47, 48,
    49; the table's last: 64)."""
    yield pytest.param(1, RAGGED + (33, 47), id="b8-s1")
    for context in (32, 33, 47, 48, 49, 64):
        yield pytest.param(32, (context,), id=f"b1-s32-ends-{context}")


@pytest.mark.parametrize("store", [None, "int8"])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("s, contexts", _walk_shapes())
def test_pallas_row_walk_matches_gather_on_ragged_rows(s, contexts, hd, store):
    """The kernel's loop over a row's own table entries (its trip count read
    from ``idx``) against the gather reference, at both head sizes the
    benchmark's cells run and both kinds of pool: a context that ends
    anywhere in a block, a full table, and a free slot."""
    rng = np.random.default_rng(29)
    q, pools, bt, idx, scales = _ragged_case(rng, contexts, s, hd, store, tail=0)
    ref = paged_attention(q, *pools, 1, bt, idx, *scales, impl="gather")
    out = paged_attention(q, *pools, 1, bt, idx, *scales, impl="pallas", interpret=True)
    tol = 1e-5 if store is None else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol)
    assert np.isfinite(np.asarray(out)).all()


#: what a tile adds to the walk's geometry: a softmax step takes ``_TILE``
#: table entries, ``SPAN`` key positions. ``WIDE_MB`` entries are two whole
#: tiles and half a third (the tile does not divide the table's width), so a
#: row's context ends one position short of a tile's end, on it, one past
#: it, in the middle of the third tile, or fills the table
SPAN = _TILE * WALK_BS
WIDE_MB = 2 * _TILE + _TILE // 2


def _tile_shapes():
    """(queries a row, block_len, the rows' contexts). A decode step; a block
    round (four queries from a block's first position, block_len 4: every
    context a multiple of 4); chunks of 32 that end at the same places."""
    ends = (SPAN - 1, SPAN, SPAN + 1, 2 * SPAN + 40, WIDE_MB * WALK_BS)
    yield pytest.param(1, 1, (1,) + ends + (None,), id="s1")
    yield pytest.param(4, 4, (4, SPAN - 4, SPAN, SPAN + 4, 2 * SPAN + 40, WIDE_MB * WALK_BS, None),
                       id="s4-B4")
    yield pytest.param(32, 1, (32,) + ends, id="s32")


@pytest.mark.parametrize("store", [None, "int8"])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("rep", [1, 4, 8])
@pytest.mark.parametrize("s, block_len, contexts", _tile_shapes())
def test_pallas_tiles_match_gather_on_a_table_wider_than_a_tile(
        s, block_len, contexts, rep, hd, store):
    """A softmax step is a tile of table entries against a kv head's whole
    query group: rows that end around a tile's edge, after several tiles and
    at the table's end, a one-entry row and a free slot in ONE call, against
    the gather reference — one query head a kv head, four and eight; both
    head sizes; a decode step, a block round and a chunk; both kinds of pool."""
    rng = np.random.default_rng(39)
    q, pools, bt, idx, scales = _ragged_case(
        rng, contexts, s, hd, store, tail=0, mb=WIDE_MB, rep=rep)
    run = lambda impl: paged_attention(q, *pools, 1, bt, idx, *scales, impl=impl,
                                       interpret=True, block_len=block_len)
    out = np.asarray(run("pallas"))
    tol = 1e-5 if store is None else 1e-4
    np.testing.assert_allclose(out, np.asarray(run("gather")), rtol=tol, atol=tol)
    assert np.isfinite(out).all()


@pytest.mark.parametrize("store", [None, "int8"])
def test_pallas_chunk_of_more_stacked_rows_than_a_grid_step_takes(store):
    """A chunk whose kv heads' groups stack to more rows than ``_ROW_BLOCK``
    (80 queries x 4 heads = 320, padded to two blocks of 256) goes a block of
    rows a grid step, each walking the row's tiles again: first chunks and
    later ones, ending around a tile's edge."""
    from accelerate_tpu.ops.paged_attention import _ROW_BLOCK

    s, rep = 80, 4
    assert _ROW_BLOCK < s * rep < 2 * _ROW_BLOCK
    rng = np.random.default_rng(13)
    q, pools, bt, idx, scales = _ragged_case(
        rng, (s, SPAN + 1, 2 * SPAN + 40), s, 64, store, tail=0, mb=WIDE_MB, rep=rep)
    run = lambda impl: paged_attention(q, *pools, 1, bt, idx, *scales, impl=impl, interpret=True)
    tol = 1e-5 if store is None else 1e-4
    np.testing.assert_allclose(np.asarray(run("pallas")), np.asarray(run("gather")),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("store", [None, "int8"])
@pytest.mark.parametrize("mb", [
    pytest.param(_TILE // 2 - 1, id="narrower-than-a-tile"),
    pytest.param(_TILE, id="one-tile"),
    pytest.param(2 * _TILE, id="two-tiles"),
    pytest.param(_TILE + 3, id="a-tile-and-three"),
])
def test_pallas_tile_clamps_to_the_tables_width(mb, store):
    """The tile is the kernel's constant or the table's width, whichever is
    smaller, and a width it does not divide ends in a part tile: rows that
    fill the table, end on its last entry's first row, and one entry."""
    rng = np.random.default_rng(3)
    contexts = (mb * WALK_BS, (mb - 1) * WALK_BS + 1, max((mb // 2) * WALK_BS, 1), 7, None)
    q, pools, bt, idx, scales = _ragged_case(rng, contexts, 1, 64, store, tail=0, mb=mb, rep=4)
    run = lambda impl: paged_attention(q, *pools, 2, bt, idx, *scales, impl=impl, interpret=True)
    tol = 1e-5 if store is None else 1e-4
    np.testing.assert_allclose(np.asarray(run("pallas")), np.asarray(run("gather")),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("store", [None, "int8"])
@pytest.mark.parametrize("s, contexts, mb", [
    pytest.param(1, RAGGED[:4] + (None, 33), WALK_MB, id="b6-s1"),
    pytest.param(32, (33,), WALK_MB, id="b1-s32"),
    # wider than a tile: rows that end in a tile's first entry (seven poisoned
    # entries behind it in the same tile, whole poisoned tiles after it), in
    # its last, half-way, and a free slot
    pytest.param(1, (1, SPAN - 1, SPAN + 1, SPAN + SPAN // 2, None, 2 * SPAN + 1), WIDE_MB,
                 id="b6-s1-wide"),
    pytest.param(32, (SPAN + 1,), WIDE_MB, id="b1-s32-wide"),
])
def test_pallas_row_walk_stops_where_the_row_does(s, contexts, mb, store):
    """Poisoned tail: every table entry past a row's last live one points at
    a block of NaN, and the output is bit-equal to the run where they point
    at the null block — the walk never touches what no query attends, be it
    in the row's last, part-filled tile or in the tiles after it."""
    outs = []
    for tail in (0, 1):
        q, pools, bt, idx, scales = _ragged_case(
            np.random.default_rng(7), contexts, s, 64, store, tail, mb=mb)
        outs.append(np.asarray(paged_attention(
            q, *pools, 2, bt, idx, *scales, impl="pallas", interpret=True)))
    assert (bt == 1).any(), "no entry was poisoned"
    assert np.isfinite(outs[1]).all()
    np.testing.assert_array_equal(outs[1], outs[0])


@pytest.mark.parametrize("store", [None, "int8"])
@pytest.mark.parametrize("contexts", [
    pytest.param((1, 2 * SPAN + 40, 1, SPAN + 1, None), id="short-long-short"),
    pytest.param((2 * SPAN + 40, 5, SPAN + 1, 1), id="long-first"),
])
def test_pallas_tile_rows_that_no_copy_wrote_do_not_reach_the_output(contexts, store):
    """A tile's buffer holds ``_TILE`` entries and a short row copies one:
    the other rows hold what the scratch held. In the Pallas interpreter
    that memory starts as NaN (a one-entry row FIRST in the call reads it:
    ``p`` is 0 there, and ``0 x NaN`` would be NaN in ``p @ v`` were V's rows
    not selected by key position), and a one-entry row AFTER a long one
    finds the long row's blocks there: neither reaches the output."""
    rng = np.random.default_rng(11)
    q, pools, bt, idx, scales = _ragged_case(rng, contexts, 1, 64, store, tail=0, mb=WIDE_MB, rep=4)
    out = np.asarray(paged_attention(
        q, *pools, 1, bt, idx, *scales, impl="pallas", interpret=True))
    assert np.isfinite(out).all()
    ref = np.asarray(paged_attention(q, *pools, 1, bt, idx, *scales, impl="gather"))
    tol = 1e-5 if store is None else 1e-4
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


# -- a chunk's call: a step of its own size -------------------------------------

#: a chunk's geometry: a call of more than ``_ROW_BLOCK`` stacked rows takes
#: ``_CHUNK_TILE`` table entries a softmax step, ``CHUNK_SPAN`` key positions,
#: its copies one loop over the tile's live entries. ``CHUNK_MB`` entries are
#: two whole wide tiles and half a third
CHUNK_SPAN = _CHUNK_TILE * WALK_BS
CHUNK_MB = 2 * _CHUNK_TILE + _CHUNK_TILE // 2
#: 72 queries x 4 heads a kv head: 288 stacked rows, one grid step of two blocks
CHUNK_S, CHUNK_REP = 72, 4
#: contexts that end inside the first wide tile, one short of its end, on it,
#: in the tile after, in the third, and at the table's end
CHUNK_ENDS = (CHUNK_S, CHUNK_SPAN - 1, CHUNK_SPAN, CHUNK_SPAN + 1, 2 * CHUNK_SPAN + 40,
              CHUNK_MB * WALK_BS)


@pytest.mark.parametrize("reference", ["gather", "lax"])
@pytest.mark.parametrize("store", [None, "int8", "fp8"])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("window", [
    pytest.param(0, id="whole-past"),
    pytest.param(CHUNK_SPAN // 2 - 3, id="window-under-a-wide-tile"),
    pytest.param(2 * CHUNK_SPAN + 50, id="window-over-two-wide-tiles"),
])
def test_pallas_chunk_takes_a_wide_tile_and_matches_the_references(window, hd, store, reference):
    """A call of more stacked rows than ``_ROW_BLOCK`` (a chunk) at the
    kernel's own constants: ``_CHUNK_TILE`` entries a softmax step, the tile's
    copies one loop, a whole tile waited for at once and a part tile entry by
    entry. Contexts that end inside a wide tile, on its end and in the tile
    after; the whole past, a window shorter than one wide tile (a tile that is
    part full at BOTH ends) and one longer than two; both head sizes; bf16-like,
    int8 and fp8 pools; against the gathered span and the scan."""
    assert CHUNK_S * CHUNK_REP > _ROW_BLOCK and _step_geometry(
        CHUNK_S * CHUNK_REP, CHUNK_MB, 2) == (_CHUNK_TILE, 2 * _ROW_BLOCK, True)
    rng = np.random.default_rng(45)
    q, pools, bt, idx, scales = _ragged_case(
        rng, CHUNK_ENDS, CHUNK_S, hd, store, tail=0, mb=CHUNK_MB, rep=CHUNK_REP)
    run = lambda impl: np.asarray(paged_attention(
        q, *pools, 1, bt, idx, *scales, impl=impl, interpret=True, window=window))
    out = run("pallas")
    tol = 1e-5 if store is None else 1e-4
    np.testing.assert_allclose(out, run(reference), rtol=tol, atol=tol)
    assert np.isfinite(out).all()


@pytest.fixture
def small_chunks(monkeypatch):
    """The kernel's constants shrunk so that tiny calls take a chunk's paths:
    more than 32 stacked rows are a chunk, whose tile is 4 entries (64
    positions); the test sets how many rows x kv heads a grid step holds."""
    monkeypatch.setattr(paged_module, "_ROW_BLOCK", 32)
    monkeypatch.setattr(paged_module, "_CHUNK_TILE", 4)
    return lambda rows: monkeypatch.setattr(paged_module, "_CHUNK_ROWS", rows)


@pytest.mark.parametrize("store", [None, "int8"])
@pytest.mark.parametrize("window", [0, 40, 150])
@pytest.mark.parametrize("head_rows, block_rows", [
    pytest.param(64, 32, id="half-a-heads-chunk"),       # j0 is 0 and 32 in turn
    pytest.param(128, 64, id="one-heads-whole-chunk"),
    pytest.param(192, 96, id="not-in-a-head"),           # 256 rows in 3 blocks of 96
])
def test_pallas_chunk_row_blocks_in_a_head_and_across_heads(
        small_chunks, head_rows, block_rows, window, store):
    """A chunk of 64 queries x 4 heads a kv head (2 kv heads) whose blocks of
    rows are half a head's chunk (a grid step's queries start at ``j0`` 0 or
    32: the window's walk starts and ends where THEY see), one head's whole
    chunk, and a block that straddles heads (it walks for all the queries):
    windows shorter than a wide tile of 64 positions and longer than two,
    contexts around a tile's edges, against the gathered span."""
    small_chunks(head_rows)
    s, rep, mb = 64, 4, 10
    assert paged_module._step_geometry(s * rep, mb, 2) == (4, block_rows, True)
    rng = np.random.default_rng(54)
    q, pools, bt, idx, scales = _ragged_case(
        rng, (64, 127, 128, 129, 160), s, 64, store, tail=0, mb=mb, rep=rep)
    run = lambda impl: np.asarray(paged_attention(
        q, *pools, 1, bt, idx, *scales, impl=impl, interpret=True, window=window))
    out = run("pallas")
    tol = 1e-5 if store is None else 1e-4
    np.testing.assert_allclose(out, run("gather"), rtol=tol, atol=tol)
    assert np.isfinite(out).all()


@pytest.mark.parametrize("window", [0, 70])
def test_pallas_chunk_walk_touches_no_entry_outside_its_span(small_chunks, window):
    """A chunk's loop of copies, like the written-out ones: every entry past
    the row's last live one - and every entry wholly behind the window of the
    row's first query - points at a block of NaN, and the output is bit-equal
    to the run where they point at the null block."""
    small_chunks(128)
    outs = []
    for tail in (0, 1):
        q, pools, bt, idx, scales = _ragged_case(
            np.random.default_rng(8), (200, 230), 64, 64, None, tail, mb=16, rep=4)
        if window:
            behind = np.maximum(idx - window + 1, 0) // WALK_BS
            for i, n in enumerate(behind):
                bt[i, :n] = tail
            assert behind.min() > 0
        outs.append(np.asarray(paged_attention(
            q, *pools, 2, bt, idx, *scales, impl="pallas", interpret=True, window=window)))
    assert (bt == 1).any() and np.isfinite(outs[1]).all()
    np.testing.assert_array_equal(outs[1], outs[0])


#: what the calls below trace to, printed by a process of its own: the jaxpr of
#: a call (the kernel's body and the call's parameters are in it) also shows
#: process-wide settings other tests change (the default matmul precision)
_ONE_BLOCK_SCRIPT = """
import hashlib, importlib, json
import jax, jax.numpy as jnp
ops = importlib.import_module("accelerate_tpu.ops.paged_attention")
S = jax.ShapeDtypeStruct

def text(b, s, rep, block_len=1, window=0, store=jnp.float32, hd=64, n_kv=2, mb=24, nb=64):
    pools = [S((3, nb, 16, n_kv * hd), store)] * 2
    scales = [S((3, nb, 16, n_kv), jnp.float32)] * 2 if store != jnp.float32 else []
    return str(jax.make_jaxpr(lambda q, bt, idx, *ps: ops.paged_attention(
        q, *ps[:2], 1, bt, idx, *ps[2:], impl="pallas", block_len=block_len, window=window))(
            S((b, s, n_kv * rep, hd), jnp.float32), S((b, mb), jnp.int32), S((b,), jnp.int32),
            *pools, *scales))

calls = {
    "decode": dict(b=3, s=1, rep=4),
    "round": dict(b=3, s=4, rep=8, block_len=4),
    "window-int8": dict(b=2, s=1, rep=7, window=40, store=jnp.int8),
    "full-block": dict(b=1, s=64, rep=4),
    "chunk": dict(b=1, s=72, rep=4),
}
out = {}
for name, shape in calls.items():
    t = text(**shape)
    out[name] = {"sha256": hashlib.sha256(t.encode()).hexdigest(), "loops": t.count("while["),
                 "vmem_limit": t.split("vmem_limit_bytes=")[1].split()[0].strip(",)")}
print("PROGRAMS " + json.dumps(out))
"""

#: sha256 of those jaxprs for the calls whose stacked rows fit one block, taken
#: on the parent commit 410bbdf: a decode step, a block round, a windowed decode
#: step over an int8 pool, a chunk of exactly ``_ROW_BLOCK`` rows. A chunk's
#: geometry must not move them
ONE_BLOCK_PROGRAMS = {
    "decode": (1 * 4, "38fb1e2436b24a1e1dead5db76d8971a0767fa132de380662dd493a58db77ecc"),
    "round": (4 * 8, "c157430a484474df39ec9df906a33770179fe9d86ba1b9e741a3f8b5fa5ce9a2"),
    "window-int8": (1 * 7, "6e7a8fb337e7a76e1e495ab0a4f867c1dd9ab9eb42234e97ab30caa19a5db6a3"),
    "full-block": (64 * 4, "d26257e9dd943e535dac3afda2b7ad922912349c28b1655f4f4feba875310f2b"),
}


@pytest.fixture(scope="module")
def traced_programs():
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", _ONE_BLOCK_SCRIPT], cwd=root, capture_output=True, text=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(next(l for l in done.stdout.splitlines() if l.startswith("PROGRAMS "))[9:])


@pytest.mark.parametrize("name", list(ONE_BLOCK_PROGRAMS))
def test_a_call_of_one_block_of_rows_is_the_parents_program(traced_programs, name):
    """``rep x s <= _ROW_BLOCK``: the geometry is ``(_TILE, one block of the
    call's rows)``, the constants are the parent's, and what the call traces
    is the parent's program to the letter - its copies written out under
    ``pl.when``, ONE loop (the walk), no limit on its VMEM - where a chunk's
    holds the loops of its copies and of its blocks of rows inside the walk's."""
    stacked, digest = ONE_BLOCK_PROGRAMS[name]
    assert (_TILE, _ROW_BLOCK) == (8, 256)
    assert _step_geometry(stacked, 1024, 8) == (8, -(-stacked // 8) * 8, False)
    assert _step_geometry(_ROW_BLOCK + 1, 1024, 8)[2]
    assert traced_programs[name] == {"sha256": digest, "loops": 1, "vmem_limit": "None"}
    chunk = traced_programs["chunk"]
    assert chunk["loops"] > 2 and chunk["vmem_limit"] == str(paged_module._CHUNK_VMEM_LIMIT)


# -- the latent kernel: one cached vector a token for every head ----------------

#: the latent walk's geometry: a pool row of 256 stored lanes - 128 of values,
#: 64 of the shared rotated key, zeros up to whole lane tiles - in blocks of
#: 16; a table of two whole tiles of the latent kernel's own and half a third
LAT_WIDTH, LAT_RANK, LAT_ROPE = 256, 128, 64
LAT_TILE = tile_entries(1 << 20, latent=True)
LAT_SPAN = LAT_TILE * WALK_BS
LAT_MB = 2 * LAT_TILE + LAT_TILE // 2


def _latent_case(rng, contexts, s, nh, mb, store):
    """A stacked latent pool ``[LAYERS, nb, 16, 256]`` of noise (an fp8 pool
    with its one scale a row) and a block table of ``mb`` entries a row: a row
    whose last query sits at ``context - 1`` holds blocks of its own for the
    entries up to that position's, the null block behind them; ``None`` is a
    free slot (position 0, the null block). Queries and rows carry zeros in
    the padding lanes, as the model hands them over."""
    nb = 1 + len(contexts) * mb
    lanes = np.arange(LAT_WIDTH) < LAT_RANK + LAT_ROPE
    pool = jnp.asarray(rng.normal(size=(LAYERS, nb, WALK_BS, LAT_WIDTH)) * lanes, jnp.float32)
    scale = None
    if store == "fp8":
        pool, scale = quantize_kv_rows(pool, kv_storage_dtype("fp8")[0])
        scale = scale[..., None]
    bt = np.zeros((len(contexts), mb), np.int32)
    idx = np.zeros((len(contexts),), np.int32)
    used = iter(rng.permutation(np.arange(1, nb)))
    for i, context in enumerate(contexts):
        if context is None:
            continue
        idx[i] = context - s
        for j in range((context - 1) // WALK_BS + 1):
            bt[i, j] = next(used)
    q = jnp.asarray(rng.normal(size=(len(contexts), s, nh, LAT_WIDTH)) * lanes, jnp.float32)
    return q, pool, bt, idx, scale


def _latent_shapes():
    """(queries a row, heads, table entries, the rows' contexts, pool). Decode
    rows whose contexts end inside the first tile, one short of its end, on
    it, one past it, several tiles in and at the table's end, a one-entry row
    first (its tile's other rows are what the scratch held: NaN in the
    interpreter) and a free slot between live rows; chunks that end at the
    same places; three heads (stacked rows padded to whole sublanes, and a
    chunk's to whole row blocks); a table narrower than a tile; an fp8 pool."""
    ends = (LAT_SPAN - 1, LAT_SPAN, LAT_SPAN + 1, 2 * LAT_SPAN + 40, LAT_MB * WALK_BS)
    yield pytest.param(1, 4, LAT_MB, (1, LAT_SPAN // 5, None) + ends, None, id="decode")
    yield pytest.param(1, 3, LAT_MB, (7, None, 2 * LAT_SPAN + 40, LAT_SPAN), None,
                       id="decode-rows-padded")
    yield pytest.param(1, 4, LAT_TILE // 2 - 1, (5, None, (LAT_TILE // 2 - 1) * WALK_BS, 33), None,
                       id="decode-table-narrower-than-a-tile")
    yield pytest.param(1, 4, LAT_MB, (3, None, LAT_SPAN + 1, 2 * LAT_SPAN + 40), "fp8",
                       id="decode-fp8")
    for context in (24, LAT_SPAN // 5) + ends:
        yield pytest.param(24, 4, LAT_MB, (context,), None, id=f"chunk-ends-{context}")
    yield pytest.param(24, 4, LAT_MB, (LAT_SPAN + 1, 2 * LAT_SPAN + 40), None, id="chunk-two-rows")
    blocks = _LATENT_ROW_BLOCK // 3 + 30            # queries a head: two row blocks of three heads
    yield pytest.param(blocks, 3, LAT_MB, (2 * LAT_SPAN + 40,), None,
                       id="chunk-rows-padded-to-two-blocks")
    yield pytest.param(24, 4, LAT_TILE // 2 - 1, (40,), None, id="chunk-table-narrower-than-a-tile")
    yield pytest.param(24, 4, LAT_MB, (LAT_SPAN + 1,), "fp8", id="chunk-fp8")


@pytest.mark.parametrize("reference", ["lax", "gather"])
@pytest.mark.parametrize("s, nh, mb, contexts, store", _latent_shapes())
def test_latent_kernel_matches_the_scan_and_the_gathered_span(s, nh, mb, contexts, store,
                                                               reference):
    """The kernel ``latent_attention`` in the Pallas interpreter against the
    scan over table entries and the gathered span: a softmax step takes a
    tile of the latent kernel's own over a block of stacked (head, query)
    rows, its live entries copied by a loop of as many trips, and rows of the
    tile that no copy wrote never reach the output."""
    q, pool, bt, idx, scale = _latent_case(np.random.default_rng(43), contexts, s, nh, mb, store)
    run = lambda impl: np.asarray(latent_attention(
        q, pool, 1, bt, idx, rank=LAT_RANK, scale=0.11, pool_scale=scale, impl=impl,
        interpret=True))
    out = run("pallas")
    assert out.shape == (len(contexts), s, nh, LAT_RANK) and np.isfinite(out).all()
    np.testing.assert_allclose(out, run(reference), rtol=2e-5, atol=2e-5)


# -- block_len: causal from block to block, bidirectional inside a block ---------


@pytest.mark.parametrize("block_len", [1, 2, 3, 4, 8])
def test_last_visible_is_the_end_of_the_querys_own_block(block_len):
    pos = np.arange(40, dtype=np.int32)
    want = (pos // block_len + 1) * block_len - 1
    np.testing.assert_array_equal(np.asarray(last_visible(jnp.asarray(pos), block_len)), want)
    np.testing.assert_array_equal(last_visible(pos, block_len), want)
    if block_len == 1:
        assert last_visible(pos, 1) is pos  # the causal rule traces nothing


def _hand_attention(q, pools, layer, bt, idx, block_len):
    """Row by row, query by query, in numpy float64: the keys a query may
    see are those at positions ``< (pos // block_len + 1) * block_len``."""
    q = np.asarray(q, np.float64)
    kp, vp = (np.asarray(p, np.float64)[layer] for p in pools)
    b, s, nh, hd = q.shape
    n_kv = kp.shape[-1] // hd
    out = np.zeros_like(q)
    for r in range(b):
        span_k = kp[bt[r]].reshape(-1, n_kv, hd)
        span_v = vp[bt[r]].reshape(-1, n_kv, hd)
        for j in range(s):
            pos = int(idx[r]) + j
            end = (pos // block_len + 1) * block_len
            for h in range(nh):
                n = h // (nh // n_kv)
                sc = span_k[:end, n] @ q[r, j, h] / np.sqrt(hd)
                p = np.exp(sc - sc.max())
                out[r, j, h] = (p / p.sum()) @ span_v[:end, n]
    return out


def _block_shapes():
    """A round's rows (s = block_len queries from a block's first position,
    ragged contexts, a free slot among them) and a chunk of 32 that starts
    and ends on a block's edge; at block_len 3 blocks straddle pages."""
    for block_len in (4, 8):
        yield pytest.param(block_len, block_len, (block_len, 16, 16 + block_len, 48, 64, None),
                           id=f"round-B{block_len}")
        yield pytest.param(block_len, 32, (48,), id=f"chunk-B{block_len}")
    yield pytest.param(3, 3, (3, 15, 18, 48, 63, None), id="round-B3")
    yield pytest.param(4, 1, (1, 15, 16, 17, 61, None), id="single-query-B4")


@pytest.mark.parametrize("block_len, s, contexts", _block_shapes())
def test_block_len_all_three_routes_against_a_hand_mask(block_len, s, contexts):
    """Query ``j`` of a row attends every position before the end of its
    own block, on the gather, lax and Pallas (interpret) routes alike, and
    as ``cached_attention`` does over the gathered span."""
    rng = np.random.default_rng(38)
    q, pools, bt, idx, _ = _ragged_case(rng, contexts, s, 64, None, tail=0)
    want = _hand_attention(q, pools, 1, bt, idx, block_len)
    for impl in ("gather", "lax", "pallas"):
        out = paged_attention(q, *pools, 1, bt, idx, impl=impl, interpret=True,
                              block_len=block_len)
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5, err_msg=impl)
    causal = paged_attention(q, *pools, 1, bt, idx, impl="lax")
    if s > 1:  # a row of several queries sees more of itself than causally
        assert np.abs(np.asarray(causal) - want).max() > 1e-3


@pytest.mark.parametrize("impl", ["gather", "lax", "pallas"])
def test_block_len_1_is_the_causal_rule_bit_for_bit(impl):
    rng = np.random.default_rng(5)
    q, pools, bt, idx, _ = _ragged_case(rng, (17, 33, 64, None), 4, 64, None, tail=0)
    plain = paged_attention(q, *pools, 2, bt, idx, impl=impl, interpret=True)
    at_one = paged_attention(q, *pools, 2, bt, idx, impl=impl, interpret=True, block_len=1)
    np.testing.assert_array_equal(np.asarray(at_one), np.asarray(plain))


def test_block_len_the_row_walk_reaches_the_blocks_end_and_no_further():
    """Poisoned tail at block_len 4: the walk's trip count is read from the
    last query's last VISIBLE position, so it visits the page that holds
    the end of the last block and none behind it."""
    outs = []
    for tail in (0, 1):
        q, pools, bt, idx, _ = _ragged_case(
            np.random.default_rng(11), (4, 16, 20, 48, None), 4, 64, None, tail)
        outs.append(np.asarray(paged_attention(
            q, *pools, 0, bt, idx, impl="pallas", interpret=True, block_len=4)))
    assert (bt == 1).any() and np.isfinite(outs[1]).all()
    np.testing.assert_array_equal(outs[1], outs[0])


def test_cached_attention_takes_the_same_block_len():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 4, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 24, 2, 8)), jnp.float32) for _ in range(2))
    idx = jnp.asarray([8, 12], jnp.int32)
    got = np.asarray(cached_attention(q, k, v, idx, block_len=4))
    for r in range(2):
        # every query of the block sees up to the block's end: the same keys
        end = int(idx[r]) + 4
        for h in range(4):
            sc = np.asarray(q[r, :, h]) @ np.asarray(k[r, :end, h // 2]).T / np.sqrt(8.0)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ np.asarray(v[r, :end, h // 2])
            np.testing.assert_allclose(got[r, :, h], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", [None, "int8"])
def test_three_routes_agree_on_a_traced_layer_of_the_stacked_pool(name):
    """Pallas-interpret against ``lax`` against ``gather`` with the layer
    index a traced scalar, as the model's layer loop hands it over: one
    compiled function serves every layer, and each layer's answer is its
    own (the pool's layers hold different rows)."""
    import jax

    dtype = None if name is None else kv_storage_dtype(name)[0]
    rng = np.random.default_rng(7)
    bt, idx, pools, q_pools = _filled_pools(rng, dtype=dtype, layer=1)
    pools = q_pools or pools
    q = jnp.asarray(rng.normal(size=(3, 2, 8, 16)).astype(np.float32))
    qi = np.maximum(idx - 1, 0)

    def route(impl):
        return jax.jit(lambda layer: paged_attention(
            q, pools[0], pools[1], layer, bt, qi, *pools[2:], impl=impl,
            interpret=True,
        ))

    outs = {impl: route(impl) for impl in ("gather", "lax", "pallas")}
    per_layer = []
    for layer in range(LAYERS):
        got = {impl: np.asarray(fn(jnp.int32(layer))) for impl, fn in outs.items()}
        np.testing.assert_allclose(got["lax"], got["gather"], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got["pallas"], got["gather"], rtol=1e-4, atol=1e-4)
        per_layer.append(got["gather"])
    if name is None:
        hand = _dense_reference(q, *pools[:2], 1, bt, qi, 4)
        np.testing.assert_allclose(per_layer[1], np.asarray(hand), rtol=1e-6, atol=1e-6)
    assert np.abs(per_layer[0] - per_layer[1]).max() > 1e-2
    assert np.abs(per_layer[2] - per_layer[1]).max() > 1e-2


def test_pallas_kernel_runs_per_head_shard_under_a_tp_mesh():
    """With the pool's kv heads sharded over ``tp`` the kernel runs under
    ``shard_map`` on each device's own heads (GSPMD cannot partition a
    Mosaic call): same numbers as the gather reference, and the output
    keeps the head sharding instead of coming back replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.ops.attention import attention_context
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    mesh = build_mesh(MeshPlugin(dp=1, tp=4), devices=jax.devices()[:4])
    rng = np.random.default_rng(5)
    dtype, _ = kv_storage_dtype("int8")
    from accelerate_tpu.parallel.sharding import (
        paged_kv_scale_sharding,
        paged_kv_sharding,
    )

    bt, idx, (kpf, vpf), pools = _filled_pools(rng, dtype=dtype, layer=2)
    q = jnp.asarray(rng.normal(size=(3, 1, 8, 16)).astype(np.float32))
    ref = paged_attention(q, *pools[:2], 2, bt, idx, *pools[2:], impl="gather")
    heads = NamedSharding(mesh, PartitionSpec(None, None, "tp", None))
    placed = [jax.device_put(q, heads)] + [
        jax.device_put(x, paged_kv_sharding(mesh, 4)) for x in pools[:2]
    ]
    placed_scales = [
        jax.device_put(x, paged_kv_scale_sharding(mesh, 4)) for x in pools[2:]
    ]
    # one kv head's lanes, whole, on each of the four devices
    assert placed[1].addressable_shards[0].data.shape == (LAYERS, 12, 4, 16)

    @jax.jit
    def run(q, kp, vp, ks, vs, layer):
        return paged_attention(q, kp, vp, layer, bt, idx, ks, vs, impl="pallas",
                               interpret=True)

    with attention_context(mesh=mesh):
        out = run(*placed, *placed_scales, jnp.int32(2))
    assert out.sharding.spec == heads.spec
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["int8", "fp8"])
def test_quantized_pool_within_tolerance(name):
    """Quantize-on-scatter + in-register dequantize: every impl agrees
    with the f32 reference within the documented per-dtype ceiling, and
    the quantized impls agree with each other much tighter (same stored
    bytes, same math)."""
    dtype, quantized = kv_storage_dtype(name)
    assert quantized
    rng = np.random.default_rng(2)
    bt, idx, (kpf, vpf), (kp, vp, ks, vs) = _filled_pools(rng, dtype=dtype)
    q = jnp.asarray(rng.normal(size=(3, 1, 8, 16)).astype(np.float32))
    ref = np.asarray(paged_attention(q, kpf, vpf, 0, bt, idx, impl="gather"))
    outs = {}
    for impl in ("lax", "gather", "pallas"):
        out = np.asarray(paged_attention(
            q, kp, vp, 0, bt, idx, k_scale=ks, v_scale=vs, impl=impl,
            interpret=True,
        ))
        assert np.abs(out - ref).max() < KV_ATOL[name], (
            f"{name}/{impl} exceeded the documented tolerance"
        )
        outs[impl] = out
    np.testing.assert_allclose(outs["lax"], outs["gather"], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("layer", [0, 1])
def test_quantized_write_respects_mask_and_drop(layer):
    """Masked lanes and out-of-range positions drop payload AND scale
    writes — the scale array can never disagree with the pool about which
    rows are real — and nothing lands in any layer but the one addressed."""
    nb, bs, n_kv, hd = 4, 4, 2, 8
    kp = jnp.zeros((LAYERS, nb, bs, n_kv * hd), jnp.int8)
    vp = jnp.zeros_like(kp)
    ks = jnp.ones((LAYERS, nb, bs, n_kv), jnp.float32)
    vs = jnp.ones_like(ks)
    bt = np.asarray([[1, 2]], np.int32)
    k = jnp.full((1, 2, n_kv, hd), 5.0)
    v = jnp.full((1, 2, n_kv, hd), 5.0)
    # lane 0 real at position 1, lane 1 masked; then a position past the
    # table span (must drop, not clamp)
    kp, vp, ks, vs = write_paged_kv(
        kp, vp, layer, k, v, bt, np.asarray([[1, 2]], np.int32),
        write_mask=np.asarray([[True, False]]), k_scale=ks, v_scale=vs,
    )
    kp, vp, ks, vs = write_paged_kv(
        kp, vp, layer, k, v, bt, np.asarray([[98, 99]], np.int32),
        write_mask=np.asarray([[True, True]]), k_scale=ks, v_scale=vs,
    )
    kp_h, ks_h = np.asarray(kp)[layer], np.asarray(ks)[layer]
    assert kp_h[1, 1].any() and ks_h[1, 1, 0] != 1.0   # the real write landed
    assert not kp_h[1, 2].any() and ks_h[1, 2, 0] == 1.0  # masked lane dropped
    assert not kp_h[2].any() and (ks_h[2] == 1.0).all()   # past-span dropped
    assert not kp_h[0].any() and not kp_h[3].any()
    others = [i for i in range(LAYERS) if i != layer]
    for pool in (kp, vp):
        assert not np.asarray(pool)[others].any()
    for scale in (ks, vs):
        assert (np.asarray(scale)[others] == 1.0).all()


@pytest.mark.parametrize("quantized", [False, True])
def test_dropped_rows_never_land_in_the_next_layer(quantized):
    """The drop rule is by ``(layer, block, offset)``: a masked lane and a
    position past the table get block id ``num_blocks``, which as a
    flattened row number (``(layer*nb + blk)*bs + off``) would be block 0
    of the NEXT layer — in range there, so written. Writing layers 0 and 1
    of three, under jit with the layer traced, leaves exactly the one real
    row of each written layer and every other row of the pool as it was."""
    import jax

    nb, bs, n_kv, hd = 3, 4, 2, 8
    dtype = jnp.int8 if quantized else jnp.float32
    pools = [jnp.zeros((LAYERS, nb, bs, n_kv * hd), dtype)] * 2
    if quantized:
        pools += [jnp.ones((LAYERS, nb, bs, n_kv), jnp.float32)] * 2
    bt = np.asarray([[1, 2], [0, 0]], np.int32)
    k = jnp.full((2, 3, n_kv, hd), 3.0)
    # row 0: position 2 real, position 3 masked, position 8 past the
    # 2-block table; row 1: a free slot (table all null block), masked
    pos = np.asarray([[2, 3, 8], [0, 1, 2]], np.int32)
    mask = np.asarray([[True, False, True], [False, False, False]])

    @jax.jit
    def write(pools, layer):
        scales = dict(zip(("k_scale", "v_scale"), pools[2:]))
        return write_paged_kv(*pools[:2], layer, k, k, bt, pos, write_mask=mask, **scales)

    for layer in (0, 1):
        pools = list(write(pools, jnp.int32(layer)))
    for pool in pools[:2]:
        h = np.asarray(pool)
        touched = np.argwhere(h.any(axis=-1))
        np.testing.assert_array_equal(touched, [[0, 1, 2], [1, 1, 2]])
    for scale in pools[2:]:
        h = np.asarray(scale)
        touched = np.argwhere((h != 1.0).any(axis=-1))
        np.testing.assert_array_equal(touched, [[0, 1, 2], [1, 1, 2]])


def test_quantize_round_trip_and_zero_rows():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 7, 16)).astype(np.float32)) * 3.0
    for name in ("int8", "fp8"):
        dtype, _ = kv_storage_dtype(name)
        q, scale = quantize_kv_rows(x, dtype)
        back = np.asarray(dequantize_kv(q, scale))
        # per-row amax scaling: relative error bounded by the format's step
        rel = np.abs(back - np.asarray(x)).max() / np.abs(np.asarray(x)).max()
        assert rel < (0.005 if name == "int8" else 0.04)
    # all-zero rows keep scale 1 and dequantize to exactly 0
    z = jnp.zeros((2, 3, 8))
    q, scale = quantize_kv_rows(z, jnp.int8)
    assert (np.asarray(scale) == 1.0).all()
    assert not np.asarray(dequantize_kv(q, scale)).any()


def test_kv_storage_dtype_policy():
    assert kv_storage_dtype("bf16") == (jnp.bfloat16, False)
    assert kv_storage_dtype("f32") == (jnp.float32, False)
    assert kv_storage_dtype("int8") == (jnp.int8, True)
    assert kv_qmax(jnp.int8) == 127.0
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        kv_storage_dtype("int4")
    with pytest.raises(ValueError, match="not a quantized"):
        kv_qmax(jnp.float32)


def test_cached_attention_gqa_grouped_einsum_matches_repeat():
    """The grouped-head einsum equals the materialised ``jnp.repeat``
    formulation to f32 noise (the satellite fix: repeated KV is never
    built). Reference computed inline with explicit repeat."""
    import jax

    rng = np.random.default_rng(4)
    b, s, nh, n_kv, hd, mc = 2, 3, 8, 2, 16, 24
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(b, mc, n_kv, hd)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(b, mc, n_kv, hd)).astype(np.float32))
    idx = np.asarray([7, 15], np.int32)

    got = cached_attention(q, kc, vc, idx)

    kr = jnp.repeat(kc, nh // n_kv, axis=2)
    vr = jnp.repeat(vc, nh // n_kv, axis=2)
    q_pos = idx[:, None] + np.arange(s)[None, :]
    valid = np.arange(mc)[None, None, :] <= q_pos[:, :, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(float(hd))
    scores = jnp.where(valid[:, None, :, :], scores, jnp.finfo(jnp.float32).min)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_paged_attention_unknown_impl_raises():
    q = jnp.zeros((1, 1, 2, 4))
    kp = jnp.zeros((1, 3, 2, 4))
    with pytest.raises(ValueError, match="unknown paged attention impl"):
        paged_attention(q, kp, kp, 0, np.zeros((1, 2), np.int32),
                        np.zeros((1,), np.int32), impl="cuda")
