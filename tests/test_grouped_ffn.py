"""The dropless expert layer's grouped rung (ops/pallas/grouped_ffn.py),
kernels in interpret mode at tiles of 16 rows, against a per-expert loop:
the values and every gradient (tokens, routing weights, the two or three
stacks) of gated and plain experts from float32 and bfloat16 products;
groups of size 0, a group that ends inside a tile, every pair on one
expert, a count equal to the capacity, and the rows past the count adding
exactly 0 to the last expert's weight gradient. Then the layer
(``nn.moe.sparse_moe`` with the kernels' gate open): every rung of the
kernels' ladder against the dense loop, the counters, ``last_routing``
and the ``FLAGS_check_nan_inf`` record. Real Mosaic lowering is
``tests/test_tpu_compile.py``'s and ``chip_smoke.py kernels``'."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.framework.bringup as bringup
from paddle_tpu.framework import nan_inf
from paddle_tpu.nn import moe
from paddle_tpu.nn.moe import _row_ladder, sparse_moe
from paddle_tpu.ops.pallas import counters
from paddle_tpu.ops.pallas import grouped_ffn as gf

F32, BF16 = jnp.float32, jnp.bfloat16
TILE = 16


@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(gf, "TILE", TILE)
    monkeypatch.setattr(gf, "SLAB", 16)     # F = 24: one slab and a tail
    monkeypatch.setattr(gf, "BLOCK", 16)    # (40 tokens: two blocks and part)
    monkeypatch.setattr(gf, "CHUNK", 8)
    # at these widths a rung's gathers and way back would outweigh any
    # product: the layer tests size their ladders by the rows alone
    monkeypatch.setattr(moe, "_grouped_cost", lambda *shape: (1.0, 0.0))
    counters.reset()
    yield
    counters.reset()


@pytest.fixture(scope="module", autouse=True)
def _no_interpreted_launch_outlives_this_file():
    """The launches are jitted and traced once a shape: what was traced
    here in interpret mode is dropped with the file."""
    yield
    jax.clear_caches()


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _loop(x, weight, picked, w_gate, w_up, w_down, dtype=F32):
    """Every held expert on every token, weighted by the token's picks of
    it: products in ``dtype`` accumulating in float32, the weight applied
    to the down product's float32 result."""
    def dot(a, b):
        return jnp.matmul(a.astype(dtype), b.astype(dtype),
                          preferred_element_type=F32)

    out = jnp.zeros((x.shape[0], w_down.shape[2]), F32)
    for e in range(w_up.shape[0]):
        up = dot(x, w_up[e]).astype(dtype).astype(F32)
        if w_gate is None:
            hidden = jnp.square(jax.nn.relu(up))
        else:
            gate = dot(x, w_gate[e]).astype(dtype).astype(F32)
            hidden = jax.nn.silu(gate) * up
        mine = jnp.sum(jnp.where(picked == e, weight, 0.0), axis=1)
        out = out + dot(hidden, w_down[e]) * mine[:, None]
    return out


def _sorted(picked, held, rows):
    """What ``sparse_moe`` hands the rung: each sorted row's slot, each
    slot's row and the rows an expert, at a capacity of ``rows``."""
    t, k = picked.shape
    slot = np.where(picked < held, picked, held).reshape(-1)
    order = np.argsort(slot, kind="stable")
    sizes = np.bincount(slot, minlength=held + 1)[:held]
    row_of_slot = np.full(t * k, -1)
    count = int(sizes.sum())
    row_of_slot[order[:count]] = np.arange(count)
    row_of_slot[row_of_slot >= rows] = -1
    slot_of_row = np.zeros(rows, np.int64)
    slot_of_row[:min(rows, t * k)] = order[:rows]
    return (jnp.asarray(slot_of_row, jnp.int32),
            jnp.asarray(row_of_slot.reshape(t, k), jnp.int32),
            jnp.asarray(sizes, jnp.int32), count)


def _picks(case, t, k, held, experts, rng):
    """(T, k) distinct experts a token, by case."""
    picked = np.argsort(rng.rand(t, experts), axis=1)[:, :k]
    if case == "one_expert":        # the held pairs all on expert 1
        picked = np.where(picked < held, held + picked, picked)
        picked[:, 0] = 1
    elif case == "empty_groups":    # experts 0 and 2 get nothing
        picked = np.where((picked == 0) | (picked == 2), held + picked,
                          picked)
    elif case == "nothing":         # no pair on a held expert
        picked = picked % (experts - held) + held
    return picked


def _inputs(gated, t=40, d=32, f=24, held=3, k=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    stacks = [jax.random.normal(ks[i], (held, d, f)) * d ** -0.5
              for i in range(2)]
    return dict(
        x=jax.random.normal(ks[2], (t, d)),
        weight=jax.random.uniform(ks[3], (t, k), minval=0.2),
        w_gate=stacks[0] if gated else None, w_up=stacks[1],
        w_down=jax.random.normal(ks[4], (held, f, d)) * f ** -0.5,
        cot=jax.random.normal(ks[5], (t, d)))


def _both(inp, picked, rows, dtype, minor_d=False):
    slot_of_row, row_of_slot, sizes, count = _sorted(
        picked, inp["w_up"].shape[0], rows)
    names = [n for n in ("x", "weight", "w_gate", "w_up", "w_down")
             if inp[n] is not None]

    def run(fn):
        def loss(*args):
            a = dict(inp, **dict(zip(names, args)))
            return jnp.sum(fn(a) * inp["cot"])
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=tuple(range(len(names))))(
                *(inp[n] for n in names))

    got = run(lambda a: gf.grouped_ffn(
        a["x"], a["weight"], slot_of_row, row_of_slot, sizes, a["w_gate"],
        a["w_up"], a["w_down"], dtype=dtype, up_minor_d=minor_d))
    want = run(lambda a: _loop(a["x"], a["weight"], jnp.asarray(picked),
                               a["w_gate"], a["w_up"], a["w_down"], dtype))
    return names, got, want, count


#: case -> (picks, capacity): 40 tokens x 2 picks of 6 experts, 3 held
CASES = {
    "mixed": ("mixed", 48),             # groups end inside tiles of 16
    "empty_groups": ("empty_groups", 48),
    "one_expert": ("one_expert", 48),
    "count_is_capacity": ("mixed", None),
}


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_grouped_rung_is_the_per_expert_loop(interp, case, gated, dtype):
    picks, rows = CASES[case]
    inp = _inputs(gated)
    picked = _picks(picks, 40, 2, 3, 6, np.random.RandomState(3))
    if rows is None:
        rows = _sorted(picked, 3, 80)[3]
    names, got, want, count = _both(inp, picked, rows, dtype,
                                    minor_d=(case == "empty_groups"))
    assert 0 < count <= rows
    # float32: rounding alone; bfloat16: the loop rounds where the rung
    # does, so what is left is the order of the sums and one rounding of
    # each cotangent that leaves in the products' type
    tol = 1e-5 if dtype == F32 else 2e-2
    np.testing.assert_allclose(float(got[0]), float(want[0]),
                               rtol=tol, atol=tol)
    for name, a, b in zip(names, got[1], want[1]):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _rel(a, b) < tol, (name, _rel(a, b))
    assert counters.snapshot() == {"moe_grouped.pallas": 1,
                                   "moe_grouped.live_tiles_only": 1}


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
def test_rows_past_the_count_add_exact_zeros(interp, gated):
    """The same pairs at a capacity with no row to spare and at one three
    tiles wider: the rows past the count go to the last expert with
    weight 0, and neither the result nor any gradient moves by a bit (a
    zero's sign aside)."""
    inp = _inputs(gated)
    picked = _picks("mixed", 40, 2, 3, 6, np.random.RandomState(4))
    count = _sorted(picked, 3, 80)[3]
    names, tight, _, _ = _both(inp, picked, count, BF16)
    _, wide, _, _ = _both(inp, picked, count + 3 * TILE, BF16)
    assert float(tight[0]) == float(wide[0])
    for name, a, b in zip(names, tight[1], wide[1]):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), name


#: case -> (picks, tokens, capacity or None for the count): the way back
WAYS_BACK = {
    "mixed": ("mixed", 40, 48),             # 40 tokens: two blocks and half
    "whole_blocks": ("mixed", 48, 64),
    "empty_groups": ("empty_groups", 40, 48),
    "one_expert": ("one_expert", 40, 48),   # runs of a whole block of rows
    "no_pair_here": ("nothing", 40, 48),    # every block still written
    "pairs_past_the_rung": ("mixed", 40, 16),
}


@pytest.mark.parametrize("case", list(WAYS_BACK))
def test_the_way_back_sums_each_tokens_rows(interp, case):
    """``_combine`` on ``_visits``' static grid against a gather of each
    slot's row: exact (a one-hot product's sum has one term, and three
    bfloat16 pieces hold a float32), whatever the routing, with as many
    visits as the shapes say and every block of tokens written."""
    picks, t, rows = WAYS_BACK[case]
    held, k, experts, width = 3, 2, 6, 24
    rng = np.random.RandomState(5)
    picked = _picks(picks, t, k, held, experts, rng)
    slot_of_row, row_of_slot, sizes, count = _sorted(picked, held, rows)
    *_, live, padded_of_slot, visits = gf._layout(
        sizes, slot_of_row, row_of_slot, TILE, gf.BLOCK, gf.CHUNK)
    launched = gf.padded_rows(rows, held)
    assert all(v.shape == (launched // gf.CHUNK + held * -(-t // gf.BLOCK),)
               for v in visits)
    values = jnp.asarray(rng.randn(launched, width) * 10.0 ** rng.randint(
        -3, 4, (launched, 1)), F32)
    token = jnp.where(live, gf._layout(
        sizes, slot_of_row, row_of_slot, TILE, gf.BLOCK, gf.CHUNK)[1] // k,
        -1).reshape(-1, 1, gf.CHUNK)
    got = gf._combine(values, token, visits, t, gf.BLOCK, gf.CHUNK)
    # a visit that matches no token does no product: NaN in every chunk
    # that holds no pair (a one-hot 0 x NaN is NaN) reaches no block
    holds = np.asarray(token).reshape(-1, gf.CHUNK).max(axis=1) >= 0
    assert not holds.all()
    unread = jnp.where(jnp.repeat(jnp.asarray(holds), gf.CHUNK)[:, None],
                       values, jnp.nan)
    assert np.array_equal(np.asarray(got), np.asarray(gf._combine(
        unread, token, visits, t, gf.BLOCK, gf.CHUNK)))
    # and it stays on the chunk of the last visit that matched
    block_of, chunk_of, look, _ = (np.asarray(v) for v in visits)
    assert np.all(holds[chunk_of[look >= 0]])
    assert np.all(chunk_of[1:][look[1:] < 0] == chunk_of[:-1][look[1:] < 0])
    at = np.asarray(padded_of_slot)
    padded = np.concatenate([np.asarray(values, np.float64),
                             np.zeros((1, width))])
    want = padded[at].sum(axis=1)
    assert got.shape == (t, width) and got.dtype == F32
    assert int((at < launched).sum()) == min(count, rows)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=3e-7, atol=0)


# ---------------------------------------------------------------------------
# the launches stop at the last live tile
# ---------------------------------------------------------------------------
#: case -> (picks, capacity; None: the count, "no_tail": a hand-made table)
TAILS = {
    "wide_tail": ("mixed", 48 + 4 * TILE),
    "no_pair_here": ("nothing", 48),        # ``held`` live tiles, no live row
    "count_is_capacity": ("mixed", None),   # the narrowest tail a rung has
    "no_tail": (None, "no_tail"),           # the gate cuts no live tile
}


def _tiles(case):
    """(``group_of_tile`` with the live count last, tiles, live tiles)."""
    picks, rows = TAILS[case]
    held = 3
    if rows == "no_tail":       # (``_layout`` always leaves a tile over)
        table = jnp.asarray([0, 0, 1, 2, 2, 5], jnp.int32)
    else:
        picked = _picks(picks, 40, 2, held, 6, np.random.RandomState(7))
        if rows is None:
            rows = _sorted(picked, held, 80)[3]
        slot_of_row, row_of_slot, sizes, _ = _sorted(picked, held, rows)
        table = gf._layout(sizes, slot_of_row, row_of_slot, TILE, gf.BLOCK,
                           gf.CHUNK)[0]
        assert table.shape == (-(-rows // TILE) + held + 1,)
    tiles, live = table.shape[0] - 1, int(table[-1])
    assert held <= live <= tiles and (live == tiles) == (case == "no_tail")
    assert (live == held) == (case == "no_pair_here")
    # every tile past the live ones is the last expert's
    assert np.all(np.asarray(table[live:tiles]) == held - 1)
    return table, tiles, live


def _planted(a, live_rows):
    """``a`` with NaN in every row from ``live_rows`` on."""
    return a.at[live_rows:].set(jnp.nan)


#: role -> (widths of the row arrays it reads, of the stacks' matrices,
#: the results' (width, type)), at D = 32, F = 24, gated
ROLE_SHAPES = {
    gf.ROLE_UP: ([32], [(32, 24)] * 2, ((24, BF16),) * 2),
    gf.ROLE_DOWN: ([24, 24, 1], [(24, 32)], ((32, F32),)),
    gf.ROLE_DHIDDEN: ([32, 24, 24, 1], [(24, 32)],
                      ((24, BF16),) * 3 + ((1, F32),)),
    gf.ROLE_DX: ([24, 24], [(32, 24)] * 2, ((32, F32),)),
}


@pytest.mark.parametrize("case", list(TAILS))
@pytest.mark.parametrize("role", list(ROLE_SHAPES))
def test_a_launch_does_no_product_on_a_tile_past_the_live_ones(
        interp, role, case):
    """NaN in every input row of the tiles past the live ones: the live
    rows of every result are the clean call's, bit for bit and finite.
    The rows past them are NOT WRITTEN (the module's contract; interpret
    mode marks what no step wrote with NaN, whatever the inputs held)."""
    table, tiles, live = _tiles(case)
    rng = np.random.RandomState(8)
    row_widths, matrices, outs = ROLE_SHAPES[role]
    arrays = [jnp.asarray(rng.randn(tiles * TILE, w), F32 if w == 1 else BF16)
              for w in row_widths]
    stacks = [jnp.asarray(rng.randn(3, *m) * 0.2, BF16) for m in matrices]
    clean = gf._row_products(role, table, arrays, stacks, outs, BF16, TILE)
    got = gf._row_products(role, table,
                           [_planted(a, live * TILE) for a in arrays],
                           stacks, outs, BF16, TILE)
    for a, b in zip(got, clean):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        assert np.all(np.isfinite(a[:live * TILE]))
        assert np.array_equal(a[:live * TILE], b[:live * TILE])
        assert np.all(np.isnan(b[live * TILE:]))


@pytest.mark.parametrize("case", list(TAILS))
def test_a_weight_gradient_sums_the_live_tiles_alone(interp, case):
    """``_weight_grad`` with NaN in both operands' rows past the live
    tiles: every expert's block is the clean call's and finite, and the
    clean call's is the per-expert sum over its own tiles."""
    table, tiles, live = _tiles(case)
    rng = np.random.RandomState(9)
    a = jnp.asarray(rng.randn(tiles * TILE, 32), BF16)
    b = jnp.asarray(rng.randn(tiles * TILE, 24), BF16)
    clean = gf._weight_grad(table, a, b, 3, TILE)
    got = gf._weight_grad(table, _planted(a, live * TILE),
                          _planted(b, live * TILE), 3, TILE)
    assert got.shape == (3, 32, 24) and np.all(np.isfinite(np.asarray(got)))
    assert np.array_equal(np.asarray(got), np.asarray(clean))
    group = np.repeat(np.asarray(table[:live]), TILE)
    a64, b64 = (np.asarray(v, np.float64)[:live * TILE] for v in (a, b))
    for e in range(3):
        want = a64[group == e].T @ b64[group == e]
        np.testing.assert_allclose(np.asarray(clean[e]), want, rtol=1e-5,
                                   atol=1e-5)


def test_what_a_rung_launches_on():
    assert gf.padded_rows(24576, 8, 512) == 28672
    assert gf.padded_rows(100, 3, 16) == (7 + 3) * 16
    work = gf.work(1024, 64, 32, True, 2)
    assert set(work["work"]) == {gf.ROLE_UP, gf.ROLE_DOWN, gf.ROLE_COMBINE}
    assert set(work["grad_work"]) == {gf.ROLE_DHIDDEN, gf.ROLE_DX,
                                      gf.ROLE_DW, gf.ROLE_COMBINE}
    # the way back as launched: a visit a chunk of rows and one more a
    # (block of tokens, expert), three passes of CHUNK x BLOCK x D each
    back = gf.work(1024, 64, 32, True, 2, tokens=512, held=4)["work"]
    assert back[gf.ROLE_COMBINE][0] == 6.0 * (
        1024 // gf.CHUNK + 4 * 512 // gf.BLOCK) * gf.CHUNK * gf.BLOCK * 64
    unit = 2.0 * 1024 * 64 * 32
    assert work["work"][gf.ROLE_UP][0] == 2 * unit
    assert gf.work(1024, 64, 32, False, 2)["grad_work"][gf.ROLE_DW][0] \
        == 2 * unit


# ---------------------------------------------------------------------------
# the layer with the kernels' gate open
# ---------------------------------------------------------------------------
def _layer_inputs(gated, tokens=256, d=16, f=8, experts=128, held=8, seed=6):
    rng = np.random.RandomState(seed)
    stacks = [jnp.asarray(0.3 * rng.randn(held, d, f), F32)
              for _ in range(2)]
    return dict(
        x=jnp.asarray(rng.randn(tokens, d), F32),
        router=jnp.asarray(rng.randn(d, experts), F32),
        gate=stacks[0] if gated else None, up=stacks[1],
        down=jnp.asarray(0.3 * rng.randn(held, f, d), F32),
        w=jnp.asarray(rng.randn(tokens, d), F32))


def _layer_loop(a, bias, top_k, held):
    scores = jax.nn.sigmoid(jnp.matmul(
        a["x"], a["router"], precision=jax.lax.Precision.HIGHEST))
    _, picked = jax.lax.top_k(scores + bias, top_k)
    weight = jnp.take_along_axis(scores, picked, axis=1)
    weight = weight / jnp.sum(weight, axis=1, keepdims=True)
    return _loop(a["x"], weight, picked, a["gate"], a["up"], a["down"])


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("held,top_k,rungs,top", [
    (8, 6, (768, 1536), "every_pair"),      # grouped under the dense rung
    (8, 2, (256, 512), "sorted"),           # grouped under a grouped top
], ids=["dense_top", "sorted_top"])
def test_every_rung_of_the_kernels_ladder_gives_the_dense_loops_layer(
        interp, gated, held, top_k, rungs, top):
    """Picks forced onto the held experts fill the higher rung, the dense
    one for the overflow where the share holds at most twice a token's
    picks; the result and every gradient stay the dense loop's, the
    counters say which rungs exist and ``last_routing`` which ran."""
    tokens, experts = 256, 128
    pairs = tokens * min(top_k, held)
    row, rung = moe._grouped_cost(tokens, top_k, held, 16, 8, gated)
    assert _row_ladder(
        pairs, held, experts, tokens * held if top == "every_pair" else 0,
        row, rung_cost=rung)[:-1] == rungs[:1]
    a = _layer_inputs(gated, tokens=tokens, experts=experts, held=held)
    names = [n for n in ("x", "router", "gate", "up", "down")
             if a[n] is not None]
    dense_rows = tokens * held
    for push, rows in ((0.0, rungs[0]),
                       (50.0, dense_rows if top == "every_pair"
                        else rungs[1])):
        bias = jnp.zeros((experts,), F32).at[:held].set(push)

        def run(fn):
            def loss(*args):
                out, routing = fn(dict(a, **dict(zip(names, args))))
                return jnp.sum(out * a["w"]), routing
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(
                    loss, argnums=tuple(range(len(names))), has_aux=True)(
                        *(a[n] for n in names))

        counters.reset()
        (got, (pairs_held, ran)), dgot = run(lambda b: sparse_moe.raw_fn(
            b["x"], b["router"], bias, b["gate"], b["up"], b["down"],
            top_k=top_k))
        snap = counters.snapshot()
        assert snap["sparse_moe.grouped"] == 1 and f"sparse_moe.{top}" in snap
        # one grouped rung under a dense top, two under a sorted one
        assert snap["moe_grouped.pallas"] == (1 if top == "every_pair"
                                              else 2)
        assert int(ran) == rows and 0 < int(pairs_held) <= rows
        (want, _), dwant = run(
            lambda b: (_layer_loop(b, bias, top_k, held), None))
        np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
        for name, g, w in zip(names, dgot, dwant):
            assert _rel(g, w) < 2e-4, (name, rows, _rel(g, w))


def test_off_the_tpu_the_layer_runs_its_plain_statement():
    """With the kernels' gate closed (this host) the sorted rungs are
    ``ragged_dot`` at its own ladder, and nothing counts a grouped rung."""
    a = _layer_inputs(True, experts=64, held=16)
    counters.reset()
    _, (pairs, ran) = sparse_moe.raw_fn(
        a["x"], a["router"], jnp.zeros((64,), F32), a["gate"], a["up"],
        a["down"], top_k=2)
    assert counters.snapshot() == {"sparse_moe.sorted": 1,
                                   "sparse_moe.gated": 1}
    assert int(ran) == _row_ladder(256 * 2, 16, 64)[0] == 512
    counters.reset()


@pytest.mark.parametrize("push,rung", [(0.0, "grouped"), (50.0, "dense")])
def test_the_record_carries_the_hidden_row_of_the_rung_that_ran(
        interp, push, rung):
    """Under ``FLAGS_check_nan_inf`` the switch hands out ``hidden`` and
    ``routed`` from whichever rung ran: the grouped rung makes its row in
    its forward rule (no probe can stand inside a ``custom_vjp``)."""
    a = _layer_inputs(True)
    bias = jnp.zeros((128,), F32).at[:8].set(push)

    class Holder:
        def named_sublayers(self):
            return []

    def loss(x, up):
        with nan_inf.recording(Holder()) as rec:
            out, routing = sparse_moe.raw_fn(
                x, a["router"], bias, a["gate"], up, a["down"], top_k=6)
            keys = [k for k, _, _ in rec.frames[0].entries]
            rows = rec.frames[0].stacked()
        return jnp.sum(out * a["w"]), (keys, rows, routing)

    (_, (keys, rows, routing)), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(a["x"], a["up"])
    assert keys == ["renorm_denominator", "hidden", "routed"]
    assert int(routing[1]) == (768 if rung == "grouped" else 256 * 8)
    hidden, routed = np.asarray(rows)[1:]
    assert hidden[0] == 0 and hidden[1] > 0 and routed[1] > 0
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)


def test_the_record_of_a_rung_with_a_wide_tail_stays_clean(interp):
    """Under ``FLAGS_check_nan_inf`` the ``hidden`` row is of the rows the
    launches wrote: six tiles past the live ones, which no step writes
    (NaN in interpret mode), leave it with no non-finite element."""
    inp = _inputs(True)
    picked = _picks("mixed", 40, 2, 3, 6, np.random.RandomState(3))
    slot_of_row, row_of_slot, sizes, _ = _sorted(picked, 3, 48 + 4 * TILE)
    table = gf._layout(sizes, slot_of_row, row_of_slot, TILE, gf.BLOCK,
                       gf.CHUNK)[0]
    assert table.shape[0] - 1 - int(table[-1]) >= 4

    class Holder:
        def named_sublayers(self):
            return []

    def loss(x, up):
        with nan_inf.recording(Holder()) as rec:
            out = gf.grouped_ffn(x, inp["weight"], slot_of_row, row_of_slot,
                                 sizes, inp["w_gate"], up, inp["w_down"],
                                 dtype=BF16)
            keys = [k for k, _, _ in rec.frames[0].entries]
            rows = rec.frames[0].stacked()
        return jnp.sum(out * inp["cot"]), (keys, rows)

    (_, (keys, rows)), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(inp["x"], inp["w_up"])
    assert keys == ["hidden"]
    nonfinite, largest, _ = np.asarray(rows)[0]
    assert nonfinite == 0 and 0 < largest < 100
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)
