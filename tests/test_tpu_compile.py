"""The new cell's Pallas kernels compiled for a DESCRIBED v5e at the
cell's real widths — no chip attached, nothing runs, no time or result
is read. What interpret mode cannot see is caught here at no chip time:
a slice the tiling refuses, more VMEM than a kernel may use (the
streaming flash backward at 8,192 x (192 + 128) needed a raised limit,
PR 26).

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file. All such tests live in this one file."""
import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BF16 = jnp.float32, jnp.bfloat16
#: the cell's KDA call: 1 x 8,192 tokens, 32 heads of 128 x 128
KDA = (1, 8192, 32, 128)


def _pallas_calls_in(jaxpr):
    """The parameters of every ``pallas_call`` of a jaxpr, nested jits
    included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls_in(sub)


def _pallas_calls(fn, *shapes):
    """The parameters of every ``pallas_call`` ``fn`` traces."""
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    return list(_pallas_calls_in(jax.make_jaxpr(fn)(*args).jaxpr))


def _pallas_grids(fn, *shapes):
    return [tuple(p["grid_mapping"].grid) for p in _pallas_calls(fn, *shapes)]


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_kda_chunk_kernels_compile_at_the_cells_shapes(one_chip, which):
    """Both launches with the G heads a grid step the dispatch picks for
    the cell's 32 heads of 128 x 128 (PR 38): the grid has H / G head
    steps, and G chains' blocks and temporaries fit the scoped VMEM the
    launch asks for."""
    from paddle_tpu.ops.pallas import kda

    b, t, h, d = KDA
    heads = kda._heads_a_step(h, d, d, kda.CHUNK)
    assert heads in (4, 8)
    launch = kda._pallas_fwd if which == "fwd" else kda._pallas_bwd
    seq = [((b, t, h * d), F32)] * 4 + [(KDA[:3], F32)]
    if which == "bwd":
        seq += [((b, h, t // kda.CHUNK, d, d), F32), ((b, t, h * d), F32)]

    def fn(*a):
        return launch(*a, kda.CHUNK)

    assert _pallas_grids(fn, *seq) == [(b, h // heads, t // kda.CHUNK)]
    out = _compile(fn, one_chip, *seq)
    assert f"kda_chunk_{which}" in out.as_text()


def test_stream_flash_compiles_at_mlas_widths_and_the_sequence_ceiling(
        one_chip):
    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q, k, v):
        return jnp.sum(fa._flash_attention_pallas(
            q, k, v, causal=True).astype(F32))

    out = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                   ((1, 8192, 32, 192), BF16), ((1, 8192, 32, 192), BF16),
                   ((1, 8192, 32, 128), BF16))
    text = out.as_text()
    assert "flash_attention_stream_fwd" in text
    assert "flash_attention_stream_bwd" in text


@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("window", [None, 1024])
def test_grouped_stream_flash_compiles_at_the_mellum_cells_shapes(
        one_chip, window, precision):
    """2 x 8,192 tokens, 32 query heads on 4 key/value heads of 128: the
    grouped backward launch keeps a key head's whole dK, dV in float32
    scratch beside a query head's resident Q, dO and dQ; under `highest`
    (chip_smoke.py) the products' operands are float32."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q, k, v):
        return jnp.sum(fa._flash_attention_pallas(
            q, k, v, causal=True, window=window).astype(F32))

    grads = jax.grad(loss, argnums=(0, 1, 2))
    with jax.default_matmul_precision(precision or "default"):
        out = _compile(grads, one_chip, ((2, 8192, 32, 128), BF16),
                       ((2, 8192, 4, 128), BF16), ((2, 8192, 4, 128), BF16))
    text = out.as_text()
    assert "flash_attention_" + ("grouped" if window is None
                                 else "window") in text
    # no array of K or V 32 heads wide: (2 * 32, 8192, 128) is Q's alone
    # (q, dq, out, dout), K and V stay (2 * 4, 8192, 128)
    assert "bf16[8,8192,128]" in text


@pytest.mark.parametrize("shape, precision, blocks", [
    # the Kimi cell's top rung: bfloat16 operands at the default precision
    ((8192, 2304, 20480), "default", (512, 256)),
    # the same under `highest` (chip_smoke.py): float32 operands
    ((8192, 2304, 20480), "highest", (256, 256)),
    # the BERT cells' 8,192-row rung
    ((8192, 768, 30592), "default", (1024, 128)),
    ((8192, 768, 30592), "highest", (1024, 128)),
])
def test_fused_xent_compiles_at_the_cells_shapes(one_chip, shape, precision,
                                                 blocks):
    """Forward, dh and dW/db with their VMEM scratch, from float32 h and
    table as the cells hand them over; the block pair follows from the
    shapes and the operands' width (fused_xent._mxu_dtype)."""
    from paddle_tpu.ops.pallas import fused_xent as fx

    n, hd, v = shape

    def loss(h, w, b, lab):
        s, c = fx._fused_xent_sums(h, w, b, lab, -100, (n,))
        return s / c

    with jax.default_matmul_precision(precision):
        out = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                       ((n, hd), F32), ((v, hd), F32), ((v,), F32),
                       ((n,), jnp.int32))
        assert fx._pick_blocks(
            n, hd, v, fx._mxu_dtype(jnp.dtype(F32)).itemsize) == blocks
    text = out.as_text()
    assert "fused_xent_fwd" in text and "fused_xent_bwd" in text


def test_per_row_fused_xent_compiles_at_the_looped_cells_shapes(one_chip):
    """A looped model's ONE head call: its four passes' 8,192 rows stacked
    against the whole 49,152-row table at hidden 2048, a loss a row out
    and a cotangent a row in, through the ladder as the step calls it: at
    the default precision, bfloat16 operands at blocks (512, 384). Under
    `highest` the picker takes (256, 384) for float32 operands, the first
    shape whose vocabulary 384 divides at hidden 2048, and dh's launch
    wants 30.97 MB of the 30.12 MB scoped limit (found here, PR 41;
    PERF.md section 7): ``chip_smoke.py``'s check of this call runs it at
    the default precision, as the cell does."""
    from paddle_tpu.ops.pallas import fused_xent as fx

    n, hd, v = 32768, 2048, 49152
    precision = "default"
    assert fx._pick_blocks(n, hd, v, 2) == (512, 384)
    assert fx._pick_blocks(n, hd, v, 4) == (256, 384)

    def loss(h, w, b, lab, weight):
        rows = fx._fused_xent_rows(h, w, b, lab, -100,
                                   fx._ladder(n, fx._blocks(h, w)[0]))
        return jnp.sum(rows * weight)

    with jax.default_matmul_precision(precision):
        out = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                       ((n, hd), F32), ((v, hd), F32), ((v,), F32),
                       ((n,), jnp.int32), ((n,), F32))
    text = out.as_text()
    for role in ("fused_xent_fwd", "fused_xent_bwd",
                 "fused_xent_rows4096_fwd", "fused_xent_rows16384_bwd"):
        assert role in text


@pytest.mark.parametrize("heads, kv_heads, d, dv, window, role", [
    # the Kanana cell's launch: 2 x 32 heads, keys 192, values 128
    (32, 32, 192, 128, None, "flash_attention_stream_bwd"),
    # the Mellum cell's: 2 x 4 key heads, 8 query heads to each
    (32, 4, 128, 128, None, "flash_attention_grouped"),
    (32, 4, 128, 128, 1024, "flash_attention_window"),
], ids=["kanana", "mellum-grouped", "mellum-windowed"])
def test_the_stream_backward_is_one_launch_under_the_vmem_cap(
        one_chip, heads, kv_heads, d, dv, window, role):
    """Since PR 42 a layer's streaming backward is ONE Mosaic custom call:
    the kernel that sums dK, dV holds the query head's whole dQ, (8192, d)
    float32 scratch and the double-buffered output block, beside the
    resident Q and dO. It lowers for the v5e at the cells' launches and
    asks for less scoped VMEM than ``_stream_params``' 96 MiB cap."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q, k, v):
        return jnp.sum(fa._flash_attention_pallas(
            q, k, v, causal=True, window=window).astype(F32))

    grads = jax.grad(loss, argnums=(0, 1, 2))
    shapes = (((2, 8192, heads, d), BF16), ((2, 8192, kv_heads, d), BF16),
              ((2, 8192, kv_heads, dv), BF16))
    launched = _pallas_calls(grads, *shapes)
    forward = "flash_attention_stream_fwd" if role.endswith("_bwd") else role
    assert [p["name"] for p in launched] == [forward, role]
    # held whole: Q, dO and dQ's block twice, dQ in float32; never the cap
    rows = 8192 * fa._lanes(d)
    asked = launched[1]["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert 2 * 2 * 2 * rows + 4 * rows < asked < 96 << 20
    text = _compile(grads, one_chip, *shapes).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    backward = [line for line in calls
                if f"transpose(jvp(jit(_flash_attention_pallas)))/pallas/"
                   f"{role}/" in line]
    assert len(calls) == 2 and len(backward) == 1
    # the one call writes all three gradients: dQ query heads wide, dK
    # and dV key heads wide
    assert (f"(bf16[{2 * heads},8192,{d}]" in backward[0]
            and f"bf16[{2 * kv_heads},8192,{dv}]" in backward[0])


def test_stream_flash_compiles_at_the_looped_cells_widths(one_chip):
    """1 x 8,192 tokens, 16 heads on 16 key heads of 128 / 128: the plain
    stream roles at a width no other cell runs them at."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q, k, v):
        return jnp.sum(fa._flash_attention_pallas(
            q, k, v, causal=True).astype(F32))

    shape = ((1, 8192, 16, 128), BF16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    shape, shape, shape).as_text()
    assert "flash_attention_stream_fwd" in text
    assert "flash_attention_stream_bwd" in text


@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("dropout_p", [0.0, 0.1])
@pytest.mark.parametrize("shape, dtype", [
    ((64, 512, 12, 64), BF16),      # the seq512 cell's: two heads a block
    ((8, 512, 4, 128), BF16),       # one head a block
    ((8, 512, 3, 64), BF16),        # odd heads: the transposing wrapper
    ((2, 512, 2, 256), F32),        # the gate's widest head
])
def test_short_flash_compiles_in_the_projections_layout(
        one_chip, shape, dtype, dropout_p, precision):
    """The short kernels through the (B, L, H*D) layout the projections
    write, forward and backward. Where that layout has a block width the
    compiled module holds the two kernels and not one `copy` or
    `transpose` of its own beside them: the reshapes cancel, and the
    barrier on the cotangent does not stand in the layout's way. Under
    `highest` (as `chip_smoke.py kernels` runs them) a float32 product
    splits its operands and the backward needs more scoped VMEM than
    Mosaic's default (PR 28)."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    b, l, h, d = shape
    seed = [((1, 1), jnp.int32)] if dropout_p else []

    def attention(q, k, v, *seed):
        return fa._flash_attention_core_short(
            *(x.reshape(shape) for x in (q, k, v)),
            seed[0] if seed else None, False, dropout_p
        ).reshape(b, l, h * d)

    def fwd_bwd(q, k, v, w, *seed):
        out, vjp = jax.vjp(lambda q, k, v: attention(q, k, v, *seed),
                           q, k, v)
        return (out,) + vjp(w)

    with jax.default_matmul_precision(precision or "default"):
        text = _compile(fwd_bwd, one_chip, *[((b, l, h * d), dtype)] * 4,
                        *seed).as_text()
    assert "flash_attention_short_fwd" in text
    assert "flash_attention_short_bwd" in text
    moved = [line for line in text.splitlines()
             if (" copy(" in line or " transpose(" in line)
             and "s32[1,1]" not in line]         # the seed, to scalar memory
    assert bool(moved) == (fa._short_block_width(h, d) is None), moved


@pytest.mark.parametrize("d, f, held, experts, top_k, score, gated", [
    (2304, 896, 16, 64, 8, "softmax", True),        # the Mellum cell's
    (2688, 1856, 8, 128, 6, "sigmoid", False),      # the Nemotron cell's
], ids=["mellum", "nemotron"])
def test_the_expert_layers_wide_rung_hands_its_gradients_over_as_stored(
        one_chip, monkeypatch, d, f, held, experts, top_k, score, gated):
    """The Mellum cell's expert layer (16,384 tokens, 16 of 64 experts of
    2304 x 896 held, softmax top 8, bfloat16 autocast) and the Nemotron
    cell's (8 of 128 plain experts of 2688 x 1856, sigmoid top 6),
    differentiated under the block's recomputation: the dense top rung is
    the ladder's one rung (the Nemotron share's sorted rung would be
    0.375 of the dense rows, over a third), plain large products (no
    loop, nothing stacked, no grouped product, no branch), and the
    (held, D, F) weight gradients leave their products in the layout the
    chip keeps the weights in: the array's own for the Mellum stack, the
    2688 minor for the Nemotron one (1856 is not whole lanes). Left free,
    XLA forms them as (held, F, D) and transposes the Mellum weight and
    both its Adam moments to match, in and out of the update (48 copies
    of 132 MB a step of that cell); pinned to the array's own order, the
    Nemotron stack gets the same copies the other way round."""
    import functools

    from paddle_tpu import amp
    from paddle_tpu.nn import moe
    from paddle_tpu.nn.moe import sparse_moe

    t = 16384
    chip, = one_chip.device_set         # the layer asks the first device
    monkeypatch.setattr(moe, "_stored_layout", functools.partial(
        moe._stored_layout, device=chip))
    assert moe._stored_layout(
        jax.ShapeDtypeStruct((held, d, f), F32)).major_to_minor == \
        ((0, 1, 2) if gated else (0, 2, 1))

    def layer(x, router, up, down, gate=None):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return sparse_moe.raw_fn(
                x, router, jnp.zeros((experts,), F32), gate, up, down,
                top_k=top_k, score_func=score)[0]

    shapes = (((t, d), BF16), ((d, experts), F32), ((held, d, f), F32),
              ((held, f, d), F32)) + ((((held, d, f), F32),) if gated else ())
    out = _compile(
        jax.grad(lambda *a: jnp.sum(jax.checkpoint(layer)(*a)),
                 argnums=tuple(range(len(shapes)))), one_chip, *shapes)
    text = out.as_text()
    # gated: three products forward, two recomputed, six backward (and the
    # router's); plain: two, one and four
    assert len(re.findall(r" convolution\(", text)) >= (11 if gated else 7)
    assert not re.findall(
        r"\b(while|dynamic-update-slice|conditional|ragged-dot)\(", text)
    assert not re.findall(
        rf"\[{held},({d},{f}|{f},{d})\]\S* (copy|transpose)\(", text)


#: the lowest rung the grouped kernels would run at in each MoE cell's
#: expert layer (``nn.moe._row_ladder`` at eight times the even share);
#: the layers' shapes are ``tools/op_bench.py``'s ``EXPERT_CELLS``
GROUPED_RUNGS = {"kimi": 16384, "mellum": 131072, "nemotron": 49152,
                 "kanana": 49152, "lfm2": 65536}


def _expert_cell(cell):
    """(tokens, D, F, held, experts, top_k, gated, scores, rung)."""
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import op_bench

    return op_bench.EXPERT_CELLS[cell] + (GROUPED_RUNGS[cell],)


@pytest.mark.parametrize("cell", list(GROUPED_RUNGS))
def test_the_expert_layers_grouped_rung_compiles_at_the_moe_cells_shapes(
        one_chip, monkeypatch, cell):
    """Each MoE cell's expert layer with the kernels' gate open, under
    bfloat16 autocast and the block's recomputation, value and gradients.
    The wide experts' shares (Nemotron's, LFM2's) run one grouped rung,
    under the dense one or, LFM2's, of every pair: the forward's two
    launches and its way back, the recomputed forward's one (its down
    product feeds nothing) and the backward's four or five (a gated expert
    has one more stack) and its way back, with no ``ragged-dot``, no
    scatter of a row array (the router's scores' cotangent is the one
    scatter, (T x E,) scalars, the parent's), and no float32 copy or
    transpose of a stack: the gradients leave the launches in the layout
    the chip keeps the stacks in (the Nemotron stack's as (held, F, D)
    blocks), and what the rung keeps for its backward is the stacks as it
    rounded them, not the switch's own operands. Nemotron's width 1856 is
    14.5 lanes: the launches take it as one whole block. The other three
    run what they ran (the Kanana and Mellum shares' narrow experts the
    dense rung alone: ``nn.moe._grouped_cost``; the Kimi share
    ``ragged_dot``, whose own ladder serves it), and their compiled
    program is, to the letter, the one compiled with the gate shut: the
    parent's."""
    import functools

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu import amp
    from paddle_tpu.nn import moe
    from paddle_tpu.nn.moe import sparse_moe
    from paddle_tpu.ops.pallas import counters

    t, d, f, held, experts, top_k, gated, score, rung = _expert_cell(cell)
    chip, = one_chip.device_set
    monkeypatch.setattr(moe, "_stored_layout", functools.partial(
        moe._stored_layout, device=chip))

    def layer(x, router, up, down, gate=None):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return sparse_moe.raw_fn(
                x, router, jnp.zeros((experts,), F32), gate, up, down,
                top_k=top_k, score_func=score)[0]

    shapes = (((t, d), BF16), ((d, experts), F32), ((held, d, f), F32),
              ((held, f, d), F32)) + ((((held, d, f), F32),) if gated else ())

    def compiled(gate_open):
        monkeypatch.setattr(bringup, "pallas_enabled", lambda: gate_open)
        counters.reset()
        text = _compile(
            jax.value_and_grad(
                lambda *a: jnp.sum(jax.checkpoint(layer)(*a)),
                argnums=tuple(range(len(shapes)))),
            one_chip, *shapes).as_text()
        return text, counters.snapshot()

    kernels = cell in ("nemotron", "lfm2")
    # (one call site: the text names the lines it was traced from)
    (text, counts), *shut = [compiled(gate_open) for gate_open in
                             ([True] if kernels else [True, False])]
    if not kernels:
        assert "sparse_moe.grouped" not in counts
        assert text == shut[0][0]
        return
    assert counts["sparse_moe.grouped"] == counts["moe_grouped.pallas"] \
        == counts["moe_grouped.live_tiles_only"] == 1
    assert ("sparse_moe.every_pair" in counts) == (cell == "nemotron")
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == (10 if gated else 9)
    assert not re.findall(r"\bragged-dot\(", text)
    assert not re.findall(r"= \w+\[\d+,[\d,]+\]\S* scatter\(", text)
    assert not re.findall(
        rf"f32\[{held},({d},{f}|{f},{d})\]\S* (copy|transpose)\(", text)


@pytest.mark.parametrize("cell", list(GROUPED_RUNGS))
def test_the_grouped_rungs_kernels_compile_under_highest(one_chip, cell):
    """The launches alone at each cell's rung under
    ``jax_default_matmul_precision=highest`` (chip_smoke.py; S12(ii): the
    TPU's ``ragged_dot`` refuses bfloat16 operands there): they name the
    MXU's own precision for bfloat16 operands, and the scoped VMEM they
    ask for (the compiler refuses a launch that needs more) holds a
    stack's whole matrix twice beside the row tiles."""
    import functools

    from paddle_tpu.ops.pallas import grouped_ffn as gf

    t, d, f, held, _, top_k, gated, _, rung = _expert_cell(cell)

    def loss(x, weight, slot_of_row, row_of_slot, sizes, up, down, gate=None):
        return jnp.sum(jax.checkpoint(functools.partial(
            gf.grouped_ffn, dtype=BF16, up_minor_d=not gated))(
                x, weight, slot_of_row, row_of_slot, sizes, gate, up, down))

    shapes = (((t, d), BF16), ((t, top_k), F32), ((rung,), jnp.int32),
              ((t, top_k), jnp.int32), ((held,), jnp.int32),
              ((held, d, f), F32), ((held, f, d), F32)) \
        + ((((held, d, f), F32),) if gated else ())
    with jax.default_matmul_precision("highest"):
        out = _compile(jax.grad(
            loss, argnums=(0, 1, 5, 6) + ((7,) if gated else ())),
            one_chip, *shapes)
    text = out.as_text()
    # no value asked for: the recomputed forward's one launch, the
    # backward's four or five and its way back
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == (7 if gated else 6)
    assert gf.padded_rows(rung, held) == rung + held * gf.TILE


@pytest.mark.parametrize("dtype,precision", [(BF16, None), (F32, "highest")],
                         ids=["bfloat16", "float32_highest"])
def test_ssd_chunk_kernels_compile_at_the_nemotron_cells_shapes(
        one_chip, monkeypatch, dtype, precision):
    """2 x 8,192 tokens, 64 heads of 64 in 8 groups, a state 128 wide:
    the scan's forward (saving the chunk states) and its hand-derived
    backward, one group's eight heads a grid step, on the projection's
    (B, T, H * P) layout. Under `highest` (chip_smoke.py) the products'
    operands are float32."""
    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import ssd

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    b, t, heads, p, groups, n = 2, 8192, 64, 64, 8, 128

    def loss(u, delta, a, bm, cm):
        return jnp.sum(ssd.ssd_chunk_scan(u, delta, a, bm, cm, groups)
                       .astype(F32))

    with jax.default_matmul_precision(precision or "default"):
        out = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
                       ((b, t, heads * p), dtype), ((b, t, heads), F32),
                       ((heads,), F32), ((b, t, groups * n), dtype),
                       ((b, t, groups * n), dtype))
    text = out.as_text()
    # two launches under the one role, and nothing of u's size is
    # transposed or copied on the way in or out
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 2
    assert text.count("ssd_chunk") >= 2
    assert not re.findall(
        rf"\[{b},{t},{heads * p}\]\S* (copy|transpose)\(", text)
    assert not re.findall(rf"\[{b},{heads},{t},{p}\]", text)


@pytest.mark.parametrize("t,dtype", [(8192, BF16), (8192, F32), (8240, BF16)],
                         ids=["bfloat16", "float32", "ragged_bfloat16"])
@pytest.mark.parametrize("stage", ["conv", "gate_norm"])
def test_mamba2_stage_kernels_compile_at_the_nemotron_cells_shapes(
        one_chip, monkeypatch, stage, t, dtype):
    """The mixer's fused element-wise stages on the projection ``[z | xBC
    | dt]`` of 2 x 8,192 tokens, 10,304 wide: forward and the one-pass
    backward, each part of xBC and each norm group found by block index
    (no sliced copy of the projection goes into a kernel), also at a
    length that is no whole number of blocks."""
    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import mamba2_stages as stages

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    b, inner, gn, heads, groups = 2, 4096, 1024, 64, 8
    total = 2 * inner + 2 * gn + heads
    if stage == "conv":
        def loss(proj, taps, bias):
            return sum(jnp.sum(o.astype(F32)) for o in stages.conv_silu(
                proj, taps, bias, inner, (inner, gn, gn)))

        shapes = [((b, t, total), dtype), ((4, inner + 2 * gn), F32),
                  ((inner + 2 * gn,), F32)]
        launches, role = 6, stages.ROLE_CONV
    else:
        def loss(y, u, proj, d_skip, weight):
            return jnp.sum(stages.gate_norm(y, u, proj, d_skip, weight,
                                            groups, 1e-5).astype(F32))

        shapes = [((b, t, inner), dtype), ((b, t, inner), dtype),
                  ((b, t, total), dtype), ((heads,), F32), ((inner,), F32)]
        launches, role = 2, stages.ROLE_NORM
    # (the value keeps the forward launches: the backward needs none of
    # their results, its residuals are the stage's inputs)
    out = _compile(jax.value_and_grad(
        loss, argnums=tuple(range(len(shapes)))), one_chip, *shapes)
    text = out.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == launches
    assert text.count(role) >= launches
    # the projection goes into the kernels as it is: no slice of it
    assert not re.findall(rf"\[{b},{t},(4096|6144)\]\S* slice\(", text)


@pytest.mark.parametrize("t,dtype", [(8192, BF16), (8192, F32), (8240, BF16)],
                         ids=["bfloat16", "float32", "ragged_bfloat16"])
@pytest.mark.parametrize("stage", ["conv", "gate_norm"])
def test_kda_stage_kernels_compile_at_the_kimi_cells_shapes(
        one_chip, monkeypatch, stage, t, dtype):
    """The KDA mixer's fused element-wise stages on the projections of
    1 x 8,192 tokens, 32 heads of 128 (PR 43): forward and the one-pass
    backward, float32 results from projections of either type, also at a
    length that is no whole number of blocks; and nothing beside the
    launches has the heads on an axis of its own."""
    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import kda_stages as stages

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    b, h, d = 1, 32, 128
    if stage == "conv":
        def loss(*a):
            outs = stages.conv_norm(*a, d)
            assert all(o.dtype == F32 for o in outs)
            return sum(jnp.sum(o) for o in outs)

        shapes = [((b, t, h * d), dtype)] * 3 + [((4, h * d), F32)] * 3
        launches, role = 6, stages.ROLE_CONV
    else:
        def loss(o, gate, weight):
            out = stages.norm_gate(o, gate, weight, 1e-5)
            assert out.dtype == F32
            return jnp.sum(out)

        shapes = [((b, t, h * d), F32), ((b, t, h * d), dtype), ((d,), F32)]
        launches, role = 2, stages.ROLE_NORM
    out = _compile(jax.value_and_grad(
        loss, argnums=tuple(range(len(shapes)))), one_chip, *shapes)
    text = out.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == launches
    assert text.count(role) >= launches
    assert f"[{b},{t},{h},{d}]" not in text


@pytest.mark.parametrize("t,dtype", [(8192, BF16), (8192, F32), (8240, BF16)],
                         ids=["bfloat16", "float32", "ragged_bfloat16"])
def test_the_gated_conv_stage_compiles_at_the_lfm2_cells_shapes(
        one_chip, monkeypatch, t, dtype):
    """The gated short convolution on the in-projection of 2 x 8,192
    tokens, three groups of 2,048 channels and 3 taps (PR 46): ONE launch
    forward and ONE backward, the result in the projection's type, also
    at a length that is no whole number of blocks; and no sliced copy of
    a column group beside the launches."""
    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import gated_conv

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    b, d = 2, 2048

    def loss(proj, taps):
        out = gated_conv.gated_conv(proj, taps)
        assert out.dtype == dtype
        return jnp.sum(out.astype(F32))

    out = _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
                   ((b, t, 3 * d), dtype), ((3, d), F32))
    text = out.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 2
    assert text.count(gated_conv.ROLE) >= 2
    assert not re.findall(rf"\[{b},{t},{d}\]\S* slice\(", text)


@pytest.mark.parametrize("precision", [None, "highest"])
def test_grouped_stream_flash_compiles_at_the_lfm2_cells_heads_of_64(
        one_chip, precision):
    """2 x 8,192 tokens, 32 query heads on 8 key/value heads of 64 (PR
    46): the stream kernels on 64-lane blocks, half a vreg, which no cell
    ran before (BERT's 64-wide heads take the short kernels)."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q, k, v):
        return jnp.sum(fa._flash_attention_pallas(
            q, k, v, causal=True).astype(F32))

    grads = jax.grad(loss, argnums=(0, 1, 2))
    with jax.default_matmul_precision(precision or "default"):
        out = _compile(grads, one_chip, ((2, 8192, 32, 64), BF16),
                       ((2, 8192, 8, 64), BF16), ((2, 8192, 8, 64), BF16))
    assert "flash_attention_grouped" in out.as_text()


# ---------------------------------------------------------------------------
# a cell's whole TrainStep, as its benchmark driver builds it
# ---------------------------------------------------------------------------
def _cell_step(cell_name, one_chip, monkeypatch):
    """(the jitted step, its arguments as shapes on the described chip)
    of a training cell at its real size, built by the cell's own driver
    from abstract parameters and an abstract optimizer state: nothing as
    large as the model is ever made here."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.framework.bringup as bringup
    from benchmarks import harness
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    cell, cfg = harness.load_cell(cell_name)
    driver = harness.load_driver(cfg)
    params = {k: jax.ShapeDtypeStruct(s, F32) for k, s in
              driver.param_shapes(driver.model_config(cfg)).items()}
    step = driver.Loop(cfg, cell, params, 1).step
    step._opt_state = jax.eval_shape(lambda: step.optimizer.init_state(
        params, dict(step.model.named_parameters())))
    ids = paddle.to_tensor(np.zeros(
        (int(cell["traffic"]["batch"]), int(cell["traffic"]["seq"])),
        "int32"))
    counters.reset()
    weights, buffers, lr, batch = step._inputs((ids, ids))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (weights, buffers, step._opt_state, lr, batch))
    return step._compiled, args


def test_the_kimi_cells_step_launches_the_kda_chunk_forward_once_a_layer(
        one_chip, monkeypatch):
    """1 x 8,192 tokens through the cell's five blocks under per-block
    recomputation: the four KDA layers' chunk forward is FOUR custom
    calls of the compiled step (eight until PR 48: the backward's second
    run of a block launched the recurrence again to write the same ``o``
    and states), every one in the forward pass and none in a
    ``rematted_computation``; four backward launches; the two stages
    either side still run again (they bring q, k, v and the gate back),
    and the whole step fits the chip with the 1.6 GB it now keeps."""
    from paddle_tpu.ops.pallas import counters

    step, args = _cell_step("kimi-linear-48b-a3b.pretrain-seq8k", one_chip,
                            monkeypatch)
    out = step.lower(*args).compile()
    snap = counters.snapshot()
    assert snap["kda_chunk.kept_across_recompute"] \
        == snap["kda_chunk.pallas"] == 4
    assert snap["flash_attention.kept_across_recompute"] == 1
    calls = [line for line in out.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    where = {role: [re.search(r'op_name="([^"]*)"', c).group(1)
                    for c in calls if f"/pallas/{role}/pallas_call" in c]
             for role in ("kda_chunk_fwd", "kda_chunk_bwd", "kda_conv",
                          "kda_gate_norm", "flash_attention_stream_fwd")}
    assert len(where["kda_chunk_fwd"]) == len(where["kda_chunk_bwd"]) == 4
    assert not any("rematted_computation" in w
                   for w in where["kda_chunk_fwd"])
    assert len(where["flash_attention_stream_fwd"]) == 1
    # three projections a layer: forward, run again, backward; one gate
    assert len(where["kda_conv"]) == 36 and len(where["kda_gate_norm"]) == 12
    assert sum("rematted_computation" in w for w in where["kda_conv"]) == 12
    mem = out.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < 16 * 2 ** 30, mem


@pytest.mark.parametrize("cell, want", [
    ("nemotron-3-nano-30b-a3b.pretrain-seq8k",
     {"ssd_chunk": 12, "mamba2_conv": 36, "mamba2_gate_norm": 12,
      "flash_attention_grouped": 2, "moe_grouped_dw": 8}),
    ("lfm2-24b-a2b.pretrain-seq8k",
     {"gated_conv": 15, "flash_attention_grouped": 2, "moe_grouped_dw": 12}),
    ("qwen3-next-80b-a3b.pretrain-seq8k", {"moe_grouped_dw": 24}),
], ids=["nemotron", "lfm2", "qwen3-next"])
def test_a_scan_or_convolution_cells_step_launches_what_its_parent_did(
        one_chip, monkeypatch, cell, want):
    """The one policy gained a name that no segment of these steps holds:
    their traced steps launch the state-space scan (forward, run again,
    backward: its outputs are NOT kept, ROADMAP S19(c)) and the gated
    convolution as often as at PR 47, and count nothing kept for KDA.
    And the grouped rung's launches that stop at their last live tile
    (PR 50) are the launches it had: four rungs' in the Nemotron and LFM2
    steps, eight in the Qwen3-Next step (two sorted rungs a layer), each
    counted ``moe_grouped.live_tiles_only`` once."""
    from paddle_tpu.ops.pallas import counters

    step, args = _cell_step(cell, one_chip, monkeypatch)
    calls = collections.Counter(
        p["name"] for p in _pallas_calls_in(step.trace(*args).jaxpr))
    rungs = 8 if cell.startswith("qwen3") else 4
    want = dict(want, moe_grouped_up=2 * rungs, moe_grouped_down=rungs,
                moe_grouped_dhidden=rungs, moe_grouped_dx=rungs,
                moe_grouped_combine=2 * rungs)
    assert {role: calls[role] for role in want} == want
    snap = counters.snapshot()
    assert snap["moe_grouped.live_tiles_only"] \
        == snap["moe_grouped.pallas"] == rungs
    if cell.startswith("qwen3"):
        return      # (its KDA launches: the test of its own step, below)
    assert "kda_chunk_fwd" not in calls
    assert not any(k.startswith("kda_chunk") for k in snap)


# ---------------------------------------------------------------------------
# the Qwen3-Next cell (PR 49): the chunk body's scalar form, heads of 256,
# a share of sorted rungs alone, and the whole step
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_kda_chunk_kernels_compile_in_the_scalar_decay_form(one_chip, which):
    """The same two launches, at the same 32 heads of 128 x 128 and the
    same heads a grid step, with the chunk formulas' ``scalar`` form (the
    C x C matrix of decays from one column of a head's block, its
    transposes and its row and column sums)."""
    from paddle_tpu.ops.pallas import kda

    b, t, h, d = KDA
    heads = kda._heads_a_step(h, d, d, kda.CHUNK)
    launch = kda._pallas_fwd if which == "fwd" else kda._pallas_bwd
    seq = [((b, t, h * d), F32)] * 4 + [(KDA[:3], F32)]
    if which == "bwd":
        seq += [((b, h, t // kda.CHUNK, d, d), F32), ((b, t, h * d), F32)]

    def fn(*a):
        return launch(*a, kda.CHUNK, True)

    assert _pallas_grids(fn, *seq) == [(b, h // heads, t // kda.CHUNK)]
    out = _compile(fn, one_chip, *seq)
    assert f"kda_chunk_{which}" in out.as_text()


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bfloat16", "float32"])
def test_the_gdn_mixer_compiles_at_the_cells_shapes(one_chip, monkeypatch,
                                                    dtype):
    """``gdn_mix`` on the projections of 1 x 8,192 tokens, 16 key heads
    under 32 value heads of 128: the convolution stage on the 16 key
    heads' width (and not on 32 copies), the two chunk launches, the gated
    norm with SiLU; value and every gradient."""
    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.nn.linear_attention import gdn_mix
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    b, t, hk, hv, d = 1, 8192, 16, 32, 128
    shapes = [((b, t, 2 * hk * d + 2 * hv * d), dtype),
              ((b, t, 2 * hv), dtype), ((4, 2 * hk * d + hv * d), F32),
              ((hv,), F32), ((hv,), F32), ((d,), F32)]

    def loss(*a):
        return jnp.sum(gdn_mix.raw_fn(*a, num_key_heads=hk,
                                      num_value_heads=hv, key_dim=d,
                                      value_dim=d))

    counters.reset()
    text = _compile(jax.value_and_grad(
        loss, argnums=tuple(range(len(shapes)))), one_chip,
        *shapes).as_text()
    snap = counters.snapshot()
    assert snap["gdn.scalar_decay"] == snap["kda_chunk.pallas"] == 1
    assert snap["kda_stage.fused"] == 2 and "kda_stage.xla" not in snap
    # conv x 3 forward and backward, the chunk pair, the gated norm pair
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 10
    for scope in ("gdn_before", "gdn_after"):
        assert scope in text


@pytest.mark.parametrize("precision", [None, "highest"])
def test_grouped_stream_flash_compiles_at_heads_of_256(one_chip, precision):
    """1 x 8,192 tokens, 16 query heads on 2 key/value heads of 256: the
    widest head the dispatch admits, which no cell ran before (the widest
    accepted are the Kanana cell's 192 / 128)."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q, k, v):
        return jnp.sum(fa._flash_attention_pallas(
            q, k, v, causal=True).astype(F32))

    grads = jax.grad(loss, argnums=(0, 1, 2))
    with jax.default_matmul_precision(precision or "default"):
        out = _compile(grads, one_chip, ((1, 8192, 16, 256), BF16),
                       ((1, 8192, 2, 256), BF16), ((1, 8192, 2, 256), BF16))
    assert "flash_attention_grouped" in out.as_text()


def test_a_share_of_sorted_rungs_alone_compiles_on_the_grouped_kernels(
        one_chip, monkeypatch):
    """32 held of 512 experts of 2048 x 512, top 10, softmax, 8,192 tokens:
    more than twice the picks are held, so the ladder has no dense rung,
    only the sorted ones of 40,960 and 81,920 rows, both through
    ``ops.pallas.grouped_ffn`` (32 groups of width 512); value and
    gradients under the block's recomputation."""
    import functools

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu import amp
    from paddle_tpu.nn import moe
    from paddle_tpu.nn.moe import _row_ladder, sparse_moe
    from paddle_tpu.ops.pallas import counters

    t, d, f, held, experts, top_k = 8192, 2048, 512, 32, 512, 10
    assert _row_ladder(t * top_k, held, experts) == (40960, 81920)
    chip, = one_chip.device_set
    monkeypatch.setattr(moe, "_stored_layout", functools.partial(
        moe._stored_layout, device=chip))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)

    def layer(x, router, up, down, gate):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return sparse_moe.raw_fn(
                x, router, jnp.zeros((experts,), F32), gate, up, down,
                top_k=top_k, score_func="softmax")[0]

    shapes = (((t, d), BF16), ((d, experts), F32), ((held, d, f), F32),
              ((held, f, d), F32), ((held, d, f), F32))
    counters.reset()
    text = _compile(
        jax.value_and_grad(lambda *a: jnp.sum(jax.checkpoint(layer)(*a)),
                           argnums=tuple(range(len(shapes)))),
        one_chip, *shapes).as_text()
    snap = counters.snapshot()
    assert snap["sparse_moe.sorted"] == snap["sparse_moe.grouped"] == 1
    assert "sparse_moe.every_pair" not in snap
    assert not re.findall(r"\bragged-dot\(", text)
    assert "moe_grouped_up" in text and "moe_grouped_combine" in text


def test_the_qwen3_next_cells_step_launches_the_recurrence_once_a_layer(
        one_chip, monkeypatch):
    """1 x 8,192 tokens through the cell's four blocks under per-block
    recomputation: THREE ``kda_chunk_fwd`` custom calls, none in a
    ``rematted_computation``, three backward ones; the attention layer's
    flash forward once; the spans ``gdn_before``, ``gdn_after`` and
    ``gated_attn`` in the compiled text; the counters of the new paths;
    and the whole step fits the chip."""
    from paddle_tpu.ops.pallas import counters

    step, args = _cell_step("qwen3-next-80b-a3b.pretrain-seq8k", one_chip,
                            monkeypatch)
    out = step.lower(*args).compile()
    snap = counters.snapshot()
    assert snap["kda_chunk.kept_across_recompute"] \
        == snap["kda_chunk.pallas"] == snap["gdn.scalar_decay"] == 3
    assert snap["flash_attention.grouped"] == 1
    assert snap["flash_attention.kept_across_recompute"] == 1
    assert snap["sparse_moe.grouped"] == snap["sparse_moe.sorted"] == 4
    # (the build's counters, ``gqa.output_gate``, ``gqa.partial_rotary``
    # and ``moe.shared_gate``, were counted before ``_cell_step`` reset
    # the table: ``tests/test_causal_lm_qwen3_next.py`` holds them)
    assert not [k for k in snap if k.endswith(".xla")], snap
    text = out.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    where = {role: [re.search(r'op_name="([^"]*)"', c).group(1)
                    for c in calls if f"/pallas/{role}/pallas_call" in c]
             for role in ("kda_chunk_fwd", "kda_chunk_bwd", "kda_conv",
                          "kda_gate_norm", "flash_attention_grouped")}
    assert len(where["kda_chunk_fwd"]) == len(where["kda_chunk_bwd"]) == 3
    assert not any("rematted_computation" in w
                   for w in where["kda_chunk_fwd"])
    assert len(where["kda_conv"]) == 27 and len(where["kda_gate_norm"]) == 9
    assert 2 <= len(where["flash_attention_grouped"]) <= 3
    for scope in ("gdn_before", "gdn_after", "gated_attn"):
        assert scope in text, scope
    mem = out.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    print(f"memory_analysis: {held / 1e9:.3f} GB held, {mem}")
    assert held < 16 * 2 ** 30, mem
