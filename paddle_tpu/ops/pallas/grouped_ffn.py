"""The dropless expert layer's grouped rung: each expert's FFN on its own
run of (token, expert) pairs sorted by expert, at a STATIC row capacity R,
as Pallas grouped products on static grids whose steps do a product only
where a pair stands: a launch's time follows the pairs it was sent.

    rows   = x[token of each sorted row]                      a gather
    gate, up = rows G[e], rows U[e]        e the row's expert, by tile
    y      = weight * (hidden(gate, up) D[e])      float32, weight in float32
    out[t] = sum of t's rows of y            one-hot products, ``_combine``

``hidden`` is ``silu(gate) * up`` for gated experts and ``relu(up)^2``
for plain ones (no ``w_gate``). Products take operands of ``dtype`` (the
autocast type) and accumulate in float32.

*A static grid, and the live tiles.* Inside, every expert's run is padded
to whole tiles of ``TILE`` rows, at least one: the LIVE tiles, whose count
follows the routing. The tiles past them are given to the last expert:
R + held x TILE rows in all, whatever the group sizes, so the shapes and
the grids are static. A padded row of a live tile carries token 0 and
weight 0: its result is multiplied by 0 in float32, the way back matches
it to no token, its cotangents are 0 and it adds exact zeros to its
expert's weight gradients. So a tile belongs to ONE expert (its weights
are picked through scalar-prefetched ``group_of_tile``, whose last element
is the count of live tiles) and nothing is masked. A grid step past the
live tiles does NO product and fetches nothing (its index maps stay on
the last live tile's blocks, which are in VMEM), as a visit of the way
back that matches no token does none. (``megablox.gmm`` sizes its grid by
the live tiles; here the grid stays the rung's and a step past them
costs under a microsecond: PERF.md section 7.10, PR 50.)

*The contract of the rows past the live tiles*: no launch writes them
and no reader reads them as numbers. Every (R', width) result of
``_row_products`` holds what the device's memory held there (NaN in
interpret mode). Its readers are the next launches, which stop at the
same tile; ``_combine``, whose visits that match no token read no row;
the routing weights' cotangent, a gather over ``padded_of_slot``, which
names live rows only; and the ``FLAGS_check_nan_inf`` row of the hidden
activations, which is taken of the live tiles' rows. (Zeros there cost
their bytes for no reader: 1.2 to 3.2 ms a layer and step on a v5e at
the three cells' shapes, PERF.md section 5, PR 50.)

*No scatter, and no gather on the way back.* The sort is a permutation,
known both ways: the rows go out by a gather over ``slot_of_row`` and come
back summed to their tokens by ``_combine``: the rows of one expert stand
in the order of their tokens, so those of a block of 256 tokens are one run
of rows, and a static grid of (token block, row chunk) visits adds to each
block the one-hot product that picks its tokens' rows out of a chunk, on
the MXU and exact in float32 (XLA's gathers cost by the ROW, one for each
of a token's ``top_k`` slots whether it has a row here or not). The
backward is the same two ways the other way round, the routing weights'
cotangent included: ONE ``jax.custom_vjp``.

*The backward by hand.* ``dhidden`` from the transposed down product, the
hidden activations rebuilt in VMEM from the kept ``gate`` / ``up`` results
(bfloat16 under autocast; nothing float32 is stored), the rows' cotangent
from the transposed gate / up products, and the weight gradients as
``rows^T x cotangents`` summed over an expert's tiles in a float32 block
that stays in VMEM while the expert does. The stacks come in as they are
stored (float32) and are rounded to ``dtype`` here, so their gradients
leave in float32 and in the layout the device keeps the stack in
(``up_minor_d``: a v5e keeps a float32 (8, 2688, 1856) stack with the 2688
minor; its gradient is then formed as (8, 1856, 2688) blocks).

Roles in a device trace and in ``counters.step_work``: ``moe_grouped_up``
(the gate and up products, one launch), ``moe_grouped_down``,
``moe_grouped_dhidden`` (the transposed down product and the element-wise
backward), ``moe_grouped_dx``, ``moe_grouped_dw`` (two or three
launches) and ``moe_grouped_combine`` (the way back, forward and
backward); each launch sits alone in a ``jax.jit`` of its own, so a step
of four expert layers traces eight or nine kernels and not forty, and
takes a stack's matrix SLAB columns at a time in a rolled loop (Mosaic
unrolls a product over its whole block: 4 MB of code a launch).
``nn.moe._grouped_ffn`` (``jax.lax.ragged_dot``, a float32
scatter-add) is the same function's plain statement, the path of the CPU
and of a multi-device trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework import nan_inf
from .counters import bump, kernel_call

_F32 = jnp.float32
#: rows a grid step; every group is padded to whole tiles
TILE = 512
#: bytes of a weight gradient's float32 block (one side of it is whole)
DW_BLOCK_BYTES = 8 << 20
#: columns (or rows) of a stack's matrix a product takes at once
SLAB = 256
#: the way back: tokens an output block, rows a chunk (TILE is whole chunks)
BLOCK, CHUNK = 256, 128
ROLE_UP, ROLE_DOWN = "moe_grouped_up", "moe_grouped_down"
ROLE_DHIDDEN, ROLE_DX, ROLE_DW = ("moe_grouped_dhidden", "moe_grouped_dx",
                                  "moe_grouped_dw")
ROLE_COMBINE = "moe_grouped_combine"
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def takes() -> bool:
    """Whether the kernels run here: on a TPU, outside a trace that XLA
    partitions over several devices."""
    from ...framework import bringup
    from ...parallel.mesh import auto_partitioned_trace

    return bringup.pallas_enabled() and not auto_partitioned_trace()


def padded_rows(rows: int, held: int, tile: int = None) -> int:
    """The rows a rung of ``rows`` launches on."""
    tile = TILE if tile is None else tile
    return (-(-rows // tile) + held) * tile


# ---------------------------------------------------------------------------
# hidden activations and their derivative, on float32 tiles
# ---------------------------------------------------------------------------
def _hidden(gate, up):
    if gate is None:
        return jnp.square(jnp.maximum(up, 0.0))
    return gate * jax.nn.sigmoid(gate) * up


def _hidden_grads(gate, up, dh):
    """(dgate or None, dup) of ``_hidden`` under the cotangent ``dh``."""
    if gate is None:
        return None, dh * 2.0 * jnp.maximum(up, 0.0)
    s = jax.nn.sigmoid(gate)
    act = gate * s
    return dh * up * (s + act * (1.0 - s)), dh * act


# ---------------------------------------------------------------------------
# the launches
# ---------------------------------------------------------------------------
def _vmem_limit(need, dtype):
    """Scoped VMEM for blocks of ``need`` bytes and what Mosaic keeps
    beside them (a product's float32 result; the several passes' operands
    of a float32 product under ``highest``, which no sum of blocks
    bounds); of 128 MiB physical."""
    if dtype == _F32:
        return 100 << 20
    return int(max(32 << 20, min(1.3 * need + (8 << 20), 100 << 20)))


def _dot(a, b, dims=_NN):
    """float32 products take the precision of the trace they are in
    (``highest`` in chip_smoke.py); the MXU's own types have one."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=_F32,
        precision=None if a.dtype == _F32 else jax.lax.Precision.DEFAULT)


def _kept(results):
    """(gate or None, up) in float32 from the kept results' tiles."""
    return ([None] + [a.astype(_F32) for a in results])[-2:]


def _live_tiles(group_of_tile):
    """The count of live tiles, ``group_of_tile``'s last element
    (``_layout``): of the array, or of its scalar-prefetched ref."""
    return group_of_tile[group_of_tile.shape[0] - 1]


def _live_tile(i, group_of_tile):
    """The tile whose blocks step ``i`` is on: its own, or past the live
    tiles the last live one, which is in VMEM already."""
    return jnp.minimum(i, _live_tiles(group_of_tile) - 1)


def _slabs(width, one):
    """``one(start, size)`` over ``width`` columns (or rows) in slabs of
    SLAB, as a ROLLED loop and a static tail. Mosaic unrolls a product
    over its whole block: on a stack's whole matrix a launch is 4 MB of
    code, a step of four expert layers 140 MB more to compile and to
    load at every start, about 3 s of ``setup_s`` (PERF.md section 6,
    PR 47)."""
    whole = width // SLAB
    if whole:
        def step(j, carry):
            one(pl.multiple_of(j * SLAB, SLAB), SLAB)
            return carry
        jax.lax.fori_loop(0, whole, step, 0)
    if width % SLAB:
        one(whole * SLAB, width % SLAB)


def _up_body(tiles, weights, outs, dtype, minor_d):
    rows = tiles[0][...]
    for w, out in zip(weights, outs):
        def one(at, n, w=w, out=out):
            part = _dot(rows, w[pl.ds(at, n), :], _NT) if minor_d \
                else _dot(rows, w[:, pl.ds(at, n)])
            out[:, pl.ds(at, n)] = part.astype(out.dtype)
        _slabs(out.shape[1], one)


def _down_body(tiles, weights, outs, dtype, minor_d):
    *results, scale = (t[...] for t in tiles)
    hidden = _hidden(*_kept(results)).astype(dtype)
    out, = outs

    def one(at, n):
        out[:, pl.ds(at, n)] = _dot(hidden, weights[0][:, pl.ds(at, n)]) \
            * scale
    _slabs(out.shape[1], one)


def _dhidden_body(tiles, weights, outs, dtype, minor_d):
    cot_ref, *kept, scale_ref = tiles
    *dkept, weighted, dscale = outs
    cot, scale = cot_ref[...], scale_ref[...]
    dscale[...] = jnp.zeros_like(dscale)

    def one(at, n):
        cols = pl.ds(at, n)
        gate, up = _kept([k[:, cols] for k in kept])
        # d / d(unweighted y) D^T
        raw = _dot(cot, weights[0][pl.ds(at, n), :], _NT)
        # as the down product took it: rounded to the products' type
        hidden = _hidden(gate, up).astype(dtype).astype(_F32)
        dgate, dup = _hidden_grads(gate, up, raw * scale)
        for ref, value in zip(dkept, [dup] if dgate is None
                              else [dgate, dup]):
            ref[:, cols] = value.astype(ref.dtype)
        weighted[:, cols] = (hidden * scale).astype(weighted.dtype)
        dscale[...] += jnp.sum(raw * hidden, axis=1, keepdims=True)
    _slabs(weighted.shape[1], one)


def _dx_body(tiles, weights, outs, dtype, minor_d):
    grads = [t[...] for t in tiles]
    out, = outs

    def one(at, n):
        out[:, pl.ds(at, n)] = sum(
            _dot(g, w[:, pl.ds(at, n)]) if minor_d
            else _dot(g, w[pl.ds(at, n), :], _NT)
            for g, w in zip(grads, weights))
    _slabs(out.shape[1], one)


_BODIES = {ROLE_UP: _up_body, ROLE_DOWN: _down_body,
           ROLE_DHIDDEN: _dhidden_body, ROLE_DX: _dx_body}


@functools.partial(jax.jit, static_argnames=("role", "outs", "dtype", "tile",
                                             "minor_d"))
def _row_products(role, group_of_tile, tiles, weights, outs, dtype, tile,
                  minor_d=False):
    """One launch over row tiles, in a ``jax.jit`` of its own (a step
    calls each once a layer and jax traces a kernel once a shape: PERF.md
    section 7.20): ``_BODIES[role]`` on the VMEM blocks of a tile of each
    array in ``tiles`` (R', W), of the whole matrix of the tile's expert
    from each stack in ``weights``, and of the (R', width) results, whose
    (width, dtype) are ``outs``. Only on the live tiles (``_layout``): a
    step past them stays on the last live tile's blocks, fetches nothing
    and does no product, and the results' rows past the live tiles are
    never written."""
    n_rows = tiles[0].shape[0]
    body = _BODIES[role]

    def kernel(g, *refs):
        ins = len(tiles) + len(weights)

        @pl.when(pl.program_id(0) < _live_tiles(g))
        def _():
            body(refs[:len(tiles)], refs[len(tiles):ins], refs[ins:], dtype,
                 minor_d)

    def by_row(width):
        return pl.BlockSpec((tile, width),
                            lambda i, g: (_live_tile(i, g), 0))

    need = 0
    for a in tiles:
        need += 2 * tile * max(a.shape[1], 128) * a.dtype.itemsize
    for w in weights:
        need += 2 * w.shape[1] * w.shape[2] * w.dtype.itemsize
    for width, result in outs:
        # the result's block pair and its float32 value before the cast
        need += tile * max(width, 128) * (2 * jnp.dtype(result).itemsize + 8)
    return kernel_call(
        role, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_rows // tile,),
            in_specs=[by_row(a.shape[1]) for a in tiles] + [
                pl.BlockSpec((None,) + w.shape[1:],
                             lambda i, g: (g[i], 0, 0)) for w in weights],
            out_specs=[by_row(width) for width, _ in outs]),
        out_shape=[jax.ShapeDtypeStruct((n_rows, width), result)
                   for width, result in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(need, weights[0].dtype)),
    )(group_of_tile, *tiles, *weights)


@functools.partial(jax.jit, static_argnames=("held", "tile"))
def _weight_grad(group_of_tile, a, b, held, tile):
    """``a^T b`` over each expert's tiles: (held, a's width, b's width)
    float32. An expert's block stays in VMEM while its tiles pass; every
    expert has a live tile, so every block is written; the steps past the
    live tiles (the last expert's, ``_layout``) fetch nothing and add
    nothing. In a ``jax.jit`` of its own, as ``_row_products``."""
    n_rows, ka = a.shape
    n = b.shape[1]
    # the widest whole-lane divisor of ``n`` whose float32 block fits
    tn = max([c for c in range(128, n + 1, 128)
              if n % c == 0 and ka * c * 4 <= DW_BLOCK_BYTES] or [128]) \
        if n % 128 == 0 else n

    def kernel(g, a_ref, b_ref, out_ref):
        i = pl.program_id(1)

        @pl.when(i < _live_tiles(g))
        def _():
            first = jnp.logical_or(i == 0, g[i] != g[jnp.maximum(i - 1, 0)])
            b = b_ref[...]

            def one(at, n):
                part = _dot(a_ref[:, pl.ds(at, n)], b, _TN)
                # (a select: what the block held before its expert's first
                # tile is not read as a number)
                out_ref[pl.ds(at, n), :] = part + jnp.where(
                    first, 0.0, out_ref[pl.ds(at, n), :])
            _slabs(ka, one)

    need = (2 * tile * (ka * a.dtype.itemsize + tn * b.dtype.itemsize)
            + 3 * ka * tn * 4)
    return kernel_call(
        ROLE_DW, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tn, n_rows // tile),
            in_specs=[pl.BlockSpec((tile, ka),
                                   lambda j, i, g: (_live_tile(i, g), 0)),
                      pl.BlockSpec((tile, tn),
                                   lambda j, i, g: (_live_tile(i, g), j))],
            out_specs=pl.BlockSpec((None, ka, tn),
                                   lambda j, i, g: (g[i], 0, j))),
        out_shape=jax.ShapeDtypeStruct((held, ka, n), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(need, a.dtype)),
    )(group_of_tile, a, b)


# ---------------------------------------------------------------------------
# the padded layout
# ---------------------------------------------------------------------------
def _layout(sizes, slot_of_row, row_of_slot, tile, block, chunk):
    """Where the sorted rows stand once every group is whole tiles:
    ``group_of_tile`` (tiles + 1,), each tile's expert and, last, the
    count of LIVE tiles (every expert's run in whole tiles, at least one
    each: the tiles past them, the last expert's, hold no pair), each
    padded row's slot and whether it holds a pair, each slot's padded row
    (the padded row count where it has none), and the way back's
    ``_visits``."""
    held, rows = sizes.shape[0], slot_of_row.shape[0]
    tiles = -(-rows // tile) + held
    # (of the pairs that have a row: the first ``rows`` of them)
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    offset = jnp.concatenate([ends[:1] * 0, ends[:-1]])     # of a group, sorted
    sizes = ends - offset
    tiles_of = jnp.maximum(1, -(-sizes // tile))
    tile_end = jnp.cumsum(tiles_of)
    first_row = (tile_end - tiles_of) * tile        # of a group, padded
    group_of_tile = jnp.minimum(held - 1, jnp.sum(
        jnp.arange(tiles, dtype=jnp.int32)[:, None] >= tile_end[None, :],
        axis=1, dtype=jnp.int32))
    group = jnp.broadcast_to(group_of_tile[:, None],
                             (tiles, tile)).reshape(-1)
    rank = jnp.arange(tiles * tile, dtype=jnp.int32) - first_row[group]
    live = rank < sizes[group]
    slot_of_padded = jnp.where(
        live, slot_of_row[jnp.clip(offset[group] + rank, 0, rows - 1)], 0)
    has_row = (row_of_slot >= 0) & (row_of_slot < ends[-1])
    group = jnp.minimum(held - 1, jnp.sum(
        row_of_slot[..., None] >= ends, axis=-1, dtype=jnp.int32))
    padded_of_slot = jnp.where(
        has_row, first_row[group] + row_of_slot - offset[group], tiles * tile)
    visits = _visits(jnp.where(has_row, group, held), first_row,
                     tiles * tile, block, chunk)
    return (jnp.concatenate([group_of_tile, tile_end[-1:].astype(jnp.int32)]),
            slot_of_padded, live, padded_of_slot, visits)


def _visits(group_of_slot, first_row, rows, block, chunk):
    """The way back's static grid. The rows stand expert by expert and,
    within an expert, in the order of their tokens, so the rows of ONE
    expert on ONE block of ``block`` tokens are a run of at most ``block``
    rows, in one or two (``block`` / ``chunk`` + 1 at most) chunks of
    ``chunk`` rows. A visit is a (token block, chunk) pair whose rows are
    summed into the block; every (block, expert) is owed one visit at
    least, so every block is written. There are at most ``rows / chunk +
    held x blocks`` of them (a chunk more only where an expert's rows pass
    a chunk's end), and the grid is that many, whatever the routing; what
    follows the routing is how many of them do a product: a visit that
    matches no token (the one an empty (block, expert) is owed, and the
    visits past the last, given to the last block) stays on the chunk of
    the last visit that matched, so it fetches nothing, and ``_combine``
    does no product on it.
    ``group_of_slot`` (T, top_k) is each slot's expert here, or ``held``
    for a slot with no row. Returns, a visit: its block, its chunk, the
    first token it looks for (below every token where it is to match
    none) and whether it is its block's first."""
    held = first_row.shape[0]
    tokens = group_of_slot.shape[0]
    blocks = -(-tokens // block)
    hit = (group_of_slot[..., None] == jnp.arange(held)).astype(jnp.int32)
    hit = jnp.pad(hit, ((0, blocks * block - tokens), (0, 0), (0, 0)))
    count = jnp.sum(hit.reshape(blocks, -1, held), axis=1)    # (blocks, held)
    start = first_row + jnp.cumsum(count, axis=0) - count     # padded row
    chunk_lo = (start // chunk).reshape(-1)
    chunks = jnp.where(count > 0, (start + count - 1) // chunk
                       - start // chunk + 1, 0).reshape(-1)
    end = jnp.cumsum(jnp.maximum(chunks, 1))        # block major, then expert
    visit = jnp.arange(rows // chunk + held * blocks, dtype=jnp.int32)
    pair = jnp.minimum(blocks * held - 1, jnp.sum(
        visit[:, None] >= end[None, :], axis=1, dtype=jnp.int32))
    nth = visit - (end - jnp.maximum(chunks, 1))[pair]
    matches = nth < chunks[pair]        # (false past the last visit too)
    stay = jax.lax.cummax(jnp.where(matches, visit, 0))
    return (pair // held,
            jnp.clip(chunk_lo[pair] + nth, 0, rows // chunk - 1)[stay],
            jnp.where(matches, pair // held * block, -block - 2),
            ((nth == 0) & (pair % held == 0)).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tokens", "block", "chunk"))
def _combine(rows, token_of_row, visits, tokens, block, chunk):
    """Each token's rows of ``rows`` (R', W) float32, summed: (tokens, W).
    ``token_of_row`` (R' / chunk, 1, chunk) is a row's token, -1 for a
    row that holds no pair. No gather and no scatter: a visit
    (``_visits``) takes a chunk of rows and adds, to its block of tokens,
    the one-hot product that picks each token's row out of it, on the
    MXU, the float32 rows as three bfloat16 pieces (exact: a token has
    one row an expert at most, so a product's sum has one term, and three
    pieces hold float32's 24 bits).
    A visit that matches no token does no product and reads no row (its
    chunk may be one that no launch wrote); where it is its block's first
    it writes the zeros the block is owed.
    XLA's way back is a gather for each of a token's ``top_k`` slots,
    about 55 ns a ROW whatever its width and whether the slot has a row
    here: 7.8 ms where this takes 3 at 16,384 x 6 slots of 2688 on a v5e
    (PERF.md section 6, PR 47)."""
    width = rows.shape[1]
    blocks = -(-tokens // block)

    def kernel(_, __, look_ref, first_ref, rows_ref, token_ref, out_ref):
        visit = pl.program_id(0)
        first = first_ref[visit] == 1
        matches = look_ref[visit] >= 0

        @pl.when(matches)
        def _():
            want = look_ref[visit] + jax.lax.broadcasted_iota(
                jnp.int32, (block, chunk), 0)
            pick = jnp.where(token_ref[...] == want, 1.0, 0.0).astype(
                jnp.bfloat16)                       # (block, chunk)

            def one(at, n):
                left = rows_ref[:, pl.ds(at, n)]
                total = None
                for _ in range(3):
                    piece = left.astype(jnp.bfloat16)
                    part = _dot(pick, piece)
                    total = part if total is None else total + part
                    left = left - piece.astype(_F32)
                # (a select: what the block held before its first visit
                # is not read as a number)
                out_ref[:, pl.ds(at, n)] = total + jnp.where(
                    first, 0.0, out_ref[:, pl.ds(at, n)])
            _slabs(width, one)

        @pl.when(jnp.logical_and(first, jnp.logical_not(matches)))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

    need = 2 * (chunk + block) * width * 4 + 3 * chunk * width * 2
    out = kernel_call(
        ROLE_COMBINE, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(visits[0].shape[0],),
            in_specs=[pl.BlockSpec((chunk, width),
                                   lambda v, b, c, *_: (c[v], 0)),
                      pl.BlockSpec((None, 1, chunk),
                                   lambda v, b, c, *_: (c[v], 0, 0))],
            out_specs=pl.BlockSpec((block, width),
                                   lambda v, b, *_: (b[v], 0))),
        out_shape=jax.ShapeDtypeStruct((blocks * block, width), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(need, jnp.bfloat16)),
    )(*visits, rows, token_of_row)
    return out if out.shape[0] == tokens else out[:tokens]


# ---------------------------------------------------------------------------
# forward and backward
# ---------------------------------------------------------------------------
def _stack(w, dtype, minor_d):
    """The stack in the products' type as the launches take it:
    (held, D, F), or (held, F, D) where the device keeps it so."""
    return (jnp.swapaxes(w, 1, 2) if minor_d else w).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "tiling", "up_minor_d"))
def _forward(x, weight, slot_of_row, row_of_slot, sizes, w_gate, w_up,
             w_down, dtype, tiling, up_minor_d):
    """(out, what the backward needs); ``tiling`` is (TILE, BLOCK, CHUNK).
    Jitted, as ``_backward`` is, for a caller that is not (``nn.moe``
    traces its whole layer once for a step's layers: PERF.md section 6,
    PR 47); XLA inlines the call."""
    tile, block, chunk = tiling
    top_k = weight.shape[1]
    group_of_tile, slot, live, padded_of_slot, visits = _layout(
        sizes, slot_of_row, row_of_slot, tile, block, chunk)
    weight_of_row = jnp.where(live, weight.reshape(-1)[slot], 0.0)[:, None]
    token = slot // top_k
    rows = x[token].astype(dtype)
    stacks = [_stack(w, dtype, up_minor_d)
              for w in (w_gate, w_up) if w is not None]
    down_stack = w_down.astype(dtype)
    kept = _row_products(
        ROLE_UP, group_of_tile, [rows], stacks,
        ((w_down.shape[1], dtype),) * len(stacks), dtype, tile, up_minor_d)
    y, = _row_products(ROLE_DOWN, group_of_tile, [*kept, weight_of_row],
                       [down_stack], ((x.shape[1], _F32),), dtype, tile)
    back = jnp.where(live, token, -1).reshape(-1, 1, chunk), visits
    # (the stacks as the launches took them, not as they came: a branch of
    # the ladder's switch that hands its own operand on as a residual makes
    # XLA copy the float32 stack, 160 MB, before the switch)
    return _combine(y, *back, x.shape[0], block, chunk), (
        rows, kept, weight_of_row, token, group_of_tile, padded_of_slot,
        back, stacks, down_stack)


@functools.partial(jax.jit, static_argnames=("dtype", "tiling", "up_minor_d"))
def _backward(res, dout, dtype, tiling, up_minor_d):
    """(dx rows summed to tokens, dweight, [dgate,] dup, ddown), float32,
    the stacks' in the order the device keeps them."""
    (rows, kept, weight_of_row, token, group_of_tile, padded_of_slot,
     back, stacks, down_stack) = res
    tile, block, chunk = tiling
    held, f, d = down_stack.shape
    cot = dout[token].astype(dtype)                 # (R', D)
    *dkept, weighted, dscale = _row_products(
        ROLE_DHIDDEN, group_of_tile, [cot, *kept, weight_of_row],
        [down_stack], ((f, dtype),) * (len(kept) + 1) + ((1, _F32),),
        dtype, tile)
    dx_rows, = _row_products(ROLE_DX, group_of_tile, dkept, stacks,
                             ((d, _F32),), dtype, tile, up_minor_d)

    def grad(a, b):
        return _weight_grad(group_of_tile, a, b, held, tile)

    # (held, F, D) blocks, the stored order, where the 2688 is minor
    d_ups = [jnp.swapaxes(grad(dk, rows), 1, 2) if up_minor_d
             else grad(rows, dk) for dk in dkept]
    dweight = dscale[:, 0].at[padded_of_slot].get(mode="fill", fill_value=0)
    return (_combine(dx_rows, *back, dout.shape[0], block, chunk), dweight,
            *d_ups, grad(weighted, cot))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _grouped(x, weight, slot_of_row, row_of_slot, sizes, w_gate, w_up,
             w_down, dtype, tiling, up_minor_d, record):
    return _grouped_fwd(x, weight, slot_of_row, row_of_slot, sizes, w_gate,
                        w_up, w_down, dtype, tiling, up_minor_d, record)[0]


def _grouped_fwd(x, weight, slot_of_row, row_of_slot, sizes, w_gate, w_up,
                 w_down, dtype, tiling, up_minor_d, record):
    """``record`` (a step built under FLAGS_check_nan_inf): the result is
    (out, the ``nan_inf.row`` of the hidden activations), which no probe
    outside this rule can reach."""
    out, res = _forward(x, weight, slot_of_row, row_of_slot, sizes, w_gate,
                        w_up, w_down, dtype, tiling, up_minor_d)
    if record:
        # (of the rows the launches wrote: the live tiles')
        kept, group_of_tile = res[1], res[4]
        hidden = _hidden(*_kept(kept)).astype(dtype)
        written = jnp.arange(hidden.shape[0]) \
            < _live_tiles(group_of_tile) * tiling[0]
        out = out, nan_inf.row(jnp.where(written[:, None], hidden, 0))
    # (and the types the cotangents leave in)
    return out, (res, [jnp.zeros((0,), a.dtype)
                       for a in (x, w_gate, w_up, w_down) if a is not None])


def _grouped_bwd(dtype, tiling, up_minor_d, record, res, dout):
    if record:
        dout, _ = dout
    res, like = res
    dx, dweight, *d_stacks = _backward(res, dout, dtype, tiling, up_minor_d)
    d_stacks = [g.astype(a.dtype) for g, a in zip(d_stacks, like[1:])]
    return (dx.astype(like[0].dtype), dweight, None, None, None,
            *([None] * (3 - len(d_stacks))), *d_stacks)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def work(rows, d, f, gated, itemsize, tokens=0, held=0):
    """``work=`` / ``grad_work=`` of one call on ``rows`` launched rows:
    each product's 2 x rows x D x F, and the bytes of the row arrays it
    reads and writes once (the stacks, read once an expert, are small
    beside them); the way back's one-hot products as launched, three
    bfloat16 passes a visit, and the chunks it reads. AS LAUNCHED: the
    work of the whole static grid, an upper bound of what a step does
    (the tiles and visits that hold no pair do none of it, and how many
    those are follows the routing, which no shape tells); no roofline
    metric reads these roles."""
    unit = 2.0 * rows * d * f
    ups = 2 if gated else 1
    wide, narrow = rows * d * itemsize, rows * f * itemsize
    visits = rows // CHUNK + held * -(-tokens // BLOCK)
    back = (6.0 * visits * CHUNK * BLOCK * d,
            4 * d * (visits * CHUNK + tokens))
    return {
        "work": {
            ROLE_UP: (ups * unit, wide + ups * narrow),
            ROLE_DOWN: (unit, ups * narrow + 2 * wide),
            ROLE_COMBINE: back},
        "grad_work": {
            ROLE_DHIDDEN: (unit, wide + (2 * ups + 1) * narrow),
            ROLE_DX: (ups * unit, ups * narrow + 2 * wide),
            ROLE_DW: ((ups + 1) * unit, (ups + 1) * (wide + narrow)),
            ROLE_COMBINE: back}}


def declare(rows, tokens, held, d, f, gated, dtype):
    """Count one dispatch of a rung of ``rows`` rows and declare its
    launches' work, AS LAUNCHED (:func:`work`); ``live_tiles_only`` says
    that the launches do a part of it, the live tiles'. ``grouped_ffn``
    does, unless told that its caller has: ``nn.moe.sparse_moe`` traces
    its rungs once for a step's layers and counts each layer's."""
    bump("moe_grouped", "pallas", **work(
        padded_rows(rows, held), d, f, gated, jnp.dtype(dtype).itemsize,
        tokens, held))
    bump("moe_grouped", "live_tiles_only")


def grouped_ffn(x, weight, slot_of_row, row_of_slot, group_sizes, w_gate,
                w_up, w_down, dtype=None, up_minor_d=False, declared=False):
    """Sum over a token's picked and held experts of weight x expert(x),
    (T, D) float32, on ``slot_of_row.shape[0]`` sorted rows.

    ``x`` (T, D); ``weight`` (T, top_k) float32, the routing weight of
    every slot; ``slot_of_row`` (R,) the flat slot ``t * top_k + j`` of
    each row, rows sorted by expert, ``group_sizes`` (held,) rows an
    expert (what stands past their sum is not read); ``row_of_slot``
    (T, top_k) the row of a slot, or anything outside ``[0, R)`` for a
    slot with none (an expert elsewhere, or a pair past R); ``w_gate``
    (held, D, F) or None for plain experts, ``w_up`` (held, D, F),
    ``w_down`` (held, F, D), as stored: the products round them to
    ``dtype`` (their own type when None) and their gradients leave in
    their own type. ``up_minor_d``: the device keeps the gate / up stacks
    with D minor, and their gradients are formed so. ``declared``: the
    caller has counted this dispatch (:func:`declare`)."""
    dtype = jnp.dtype(w_up.dtype if dtype is None else dtype)
    held, d, f = w_up.shape
    if not declared:
        declare(slot_of_row.shape[0], x.shape[0], held, d, f,
                w_gate is not None, dtype)
    record = nan_inf.record is not None
    out = _grouped(x, weight.astype(_F32), slot_of_row.astype(jnp.int32),
                   row_of_slot.astype(jnp.int32),
                   group_sizes.astype(jnp.int32), w_gate, w_up, w_down,
                   dtype, (TILE, BLOCK, CHUNK), bool(up_minor_d), record)
    if record:
        out, hidden_row = out
        nan_inf.probe_row("hidden", hidden_row)
    return out
