"""A segment that ``optimizer.meta.recompute`` runs again in the backward
keeps what its KDA chunk kernel wrote (PR 48, after
``tests/test_recompute_keeps_flash.py``): the gradient of a recomputed
stack of KDA layers holds ONE ``kda_chunk_fwd`` launch a layer, its
numbers are those of plain ``jax.checkpoint`` and of no recomputation
bit for bit, the checkpoint keeps ``o`` and the chunk states and nothing
else the size of the sequence, the dispatch counts
``kda_chunk.kept_across_recompute``, the XLA form names and counts
nothing, and outside a checkpoint the name lowers to nothing. CPU,
``pallas_call`` in interpret mode; the chip's launches are
``tests/test_tpu_compile.py``'s and ``tools/profile_step.py``'s to
show."""
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.framework import nan_inf
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.ops.pallas import counters, kda
from paddle_tpu.optimizer import meta
from paddle_tpu.optimizer.meta import recompute
from tests.test_recompute_keeps_flash import (  # noqa: F401
    _holding, _saved, interp, launches)

HIDDEN, LAYERS = 64, 2
#: name -> (batch, tokens, heads, head width): the kernels take heads of
#: 128; ``ragged`` is no multiple of the 64-token chunk (padded inside
#: the dispatch), ``narrow`` goes to the XLA form
KINDS = {"two_heads": (2, 128, 2, 128), "ragged": (1, 96, 2, 128),
         "four_heads": (1, 64, 4, 128)}
NARROW = (2, 128, 2, 16)
FWD, BWD = "kda_chunk_fwd", "kda_chunk_bwd"
COUNTER = "kda_chunk.kept_across_recompute"


class Block(nn.Layer):
    """``x + KDA(x)``: what a decoder block keeps of its mixer, with the
    projections and the two stages that bring q, k, v, g, beta back."""

    def __init__(self, heads, head_dim):
        super().__init__()
        self.mixer = nn.KimiDeltaAttention(HIDDEN, heads, head_dim)

    def forward(self, x):
        return x + self.mixer(x)


def _stack(shape):
    b, t, heads, d = shape
    paddle.seed(0)
    blocks = [Block(heads, d) for _ in range(LAYERS)]
    params = [p for blk in blocks for p in blk.parameters()]
    x = jax.random.normal(jax.random.key(1), (b, t, HIDDEN))
    return blocks, params, x, [p.value for p in params]


def _loss(blocks, params, mode):
    """The stack's loss as a function of (input, parameter values), each
    block through ``recompute`` (``"kept"`` and ``"plain"``) or called
    as it is (``"none"``); traced as ``TrainStep`` traces."""
    def loss(xv, pv):
        x = Tensor(xv)
        with _holding(params, pv):
            for blk in blocks:
                x = blk(x) if mode == "none" else recompute(blk, x)
        return jnp.sum(x.value.astype(jnp.float32) ** 2)

    return loss


def _value_and_grad(blocks, params, mode, monkeypatch):
    """Loss and gradients (input, every parameter); ``"plain"``:
    ``jax.checkpoint`` with no policy, ``recompute`` as the parent had
    it for this kernel."""
    if mode == "plain":
        monkeypatch.setattr(meta, "_kept_policy", lambda: None)
    return jax.value_and_grad(_loss(blocks, params, mode), argnums=(0, 1))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_recomputed_kda_layer_launches_its_chunk_forward_once(
        interp, monkeypatch, kind):
    blocks, params, x, values = _stack(KINDS[kind])
    got, snaps = {}, {}
    for mode in ("kept", "plain", "none"):
        with monkeypatch.context() as mp:
            counters.reset()
            f = _value_and_grad(blocks, params, mode, mp)
            calls = launches(jax.make_jaxpr(f)(x, values).jaxpr)
            got[mode] = {role: calls[role] for role in (FWD, BWD)}
            snaps[mode] = counters.snapshot()
            got[mode, "values"] = jax.jit(f)(x, values)
    assert got["kept"] == got["none"] == {FWD: LAYERS, BWD: LAYERS}
    assert got["plain"] == {FWD: 2 * LAYERS, BWD: LAYERS}
    # the counter says what the dispatch saw (a segment that will be run
    # again), whatever policy the segment then has; absent outside
    for mode in ("kept", "plain"):
        assert snaps[mode][COUNTER] == snaps[mode]["kda_chunk.pallas"] \
            == LAYERS
    assert COUNTER not in snaps["none"]
    assert "kda_chunk.xla" not in snaps["kept"]
    want = jax.tree_util.tree_leaves(got["kept", "values"])
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in want)
    assert all(bool(jnp.any(a != 0)) for a in want)
    for mode in ("plain", "none"):
        for a, b in zip(want,
                        jax.tree_util.tree_leaves(got[mode, "values"])):
            assert bool(jnp.all(a == b)), (kind, mode)


def test_the_record_of_check_nan_inf_reads_the_kept_states(interp,
                                                           monkeypatch):
    """A step built with ``FLAGS_check_nan_inf`` hands ``kda_states``'s
    row out of the forward rule and out of the segment's checkpoint: the
    states are named before the row reads them, so the record rides the
    same policy and the launches are the unrecorded step's."""
    blocks, params, x, values = _stack(KINDS["two_heads"])
    f = _value_and_grad(blocks, params, "kept", monkeypatch)
    bare = jax.jit(f)(x, values)
    model = nn.LayerList(blocks)

    seen = {}

    def recorded(xv, pv):
        """As ``TrainStep`` builds a step under the flag: the forward
        rows leave the differentiated function beside its loss."""
        def loss(xv, pv):
            rec = nan_inf.record
            # where kda_mix's gradient probes send their rows (unread)
            rec.sink = jnp.zeros((nan_inf.GRAD_SLOTS, 3), jnp.float32)
            value = _loss(blocks, params, "kept")(xv, pv)
            seen["keys"] = [e[0] for e in rec.frames[0].entries]
            return value, rec.frames[0].stacked()

        with nan_inf.recording(model):
            (value, rows), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(xv, pv)
        return (value, grads), rows

    counters.reset()
    jaxpr = jax.make_jaxpr(recorded)(x, values).jaxpr
    snap = counters.snapshot()
    out, rows = jax.jit(recorded)(x, values)
    calls = launches(jaxpr)
    assert {r: calls[r] for r in (FWD, BWD)} == {FWD: LAYERS, BWD: LAYERS}
    assert snap[COUNTER] == LAYERS
    at = [i for i, key in enumerate(seen["keys"])
          if str(key).endswith("kda_states")]
    assert len(at) == LAYERS, seen["keys"]
    # finite states, and not all zero: the chunks after a row's first
    # start from what the one before wrote
    assert not bool(jnp.any(rows[:, 0]))
    assert all(float(rows[i, 1]) > 0 for i in at)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(bare)):
        assert bool(jnp.all(a == b))


@pytest.mark.parametrize("why", ["cpu_backend", "narrow_heads"])
def test_the_xla_form_names_and_counts_nothing(request, monkeypatch, why):
    """On the CPU as it is, and on heads the kernels do not take with the
    gate open, the chunk formulas run under ``lax.scan``: nothing is
    named, nothing is kept and nothing is counted, so no CPU-lowered
    text moves."""
    if why == "narrow_heads":
        request.getfixturevalue("interp")
    counters.reset()
    blocks, params, x, values = _stack(
        NARROW if why == "narrow_heads" else KINDS["two_heads"])
    f = _value_and_grad(blocks, params, "kept", monkeypatch)
    text = str(jax.make_jaxpr(f)(x, values))
    snap = counters.snapshot()
    counters.reset()
    assert snap["kda_chunk.xla"] == LAYERS
    assert COUNTER not in snap and "kda_chunk.pallas" not in snap
    assert kda.KEPT not in text


def test_the_one_policy_names_the_three_kept_values():
    """ONE cached object for every segment (PR 40), and it keeps exactly
    the three names: the flash kernels', the KDA chunk kernel's, the
    expert layer's sort."""
    from paddle_tpu.nn import moe
    from paddle_tpu.ops.pallas import flash_attention as fa

    assert meta._kept_policy() is meta._kept_policy()
    names = {fa.KEPT, kda.KEPT, moe.KEPT}
    assert len(names) == 3

    def kept_by_the_policy(name):
        from jax._src.ad_checkpoint import saved_residuals
        from jax.ad_checkpoint import checkpoint_name

        f = jax.checkpoint(
            lambda x: jnp.sum(jnp.sin(checkpoint_name(jnp.cos(x), name))),
            policy=meta._kept_policy())
        return any("argument" not in why for _, why in saved_residuals(
            f, jnp.ones((8,))))

    assert all(kept_by_the_policy(n) for n in names)
    assert not kept_by_the_policy("ssd_chunk_out_states")


# ---------------------------------------------------------------------------
# what a recomputed block keeps
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["two_heads", "ragged"])
def test_a_recomputed_block_keeps_its_inputs_o_and_the_chunk_states(
        interp, kind):
    """Arguments (the input, the parameters), the kernel's ``o`` and
    chunk states under their name, and nothing else as large as the
    sequence: not q, k, v, g, beta, not the cumulative decay ``gc``, not
    a stage's intermediate."""
    b, t, heads, d = KINDS[kind]
    paddle.seed(0)
    block = Block(heads, d)
    n_params, residuals = _saved(
        block, jax.random.normal(jax.random.key(5), (b, t, HIDDEN)))
    args = [aval for aval, why in residuals if "argument" in why]
    assert len(args) == 1 + n_params
    padded = -(-t // kda.CHUNK) * kda.CHUNK
    large = [(aval.shape, why) for aval, why in residuals
             if "argument" not in why and aval.size >= t]
    assert sorted(shape for shape, _ in large) == sorted([
        (b, padded, heads * d), (b, heads, padded // kda.CHUNK, d, d)]), \
        residuals
    # (listed at the call whose forward rule names them)
    assert all("chunk_kda_flat" in why for _, why in large), large
    assert any(kda.KEPT in why for _, why in large), large
    assert counters.snapshot()[COUNTER] == 1


# ---------------------------------------------------------------------------
# outside a checkpoint the name is nothing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wrapped", ["bare", "plain_checkpoint"])
def test_a_kda_layer_outside_recompute_lowers_to_the_parents_text(
        interp, wrapped):
    """The layer differentiated as it is, and under a ``jax.checkpoint``
    with no policy (``chip_smoke.py``'s and ``tests/test_kda.py``'s
    use): the name changes no character of the lowered text."""
    b, t, heads, d = KINDS["two_heads"]
    paddle.seed(0)
    block = Block(heads, d)
    params = list(block.parameters())

    def grads(xv, pv):
        def loss(xv, pv):
            with _holding(params, pv):
                return jnp.sum(block(Tensor(xv)).value)
        if wrapped == "plain_checkpoint":
            loss = jax.checkpoint(loss)
        return jax.grad(loss, argnums=(0, 1))(xv, pv)

    shapes = (jax.ShapeDtypeStruct((b, t, HIDDEN), jnp.float32),
              [jax.ShapeDtypeStruct(tuple(p.shape), jnp.float32)
               for p in params])

    def lowered(named):
        with pytest.MonkeyPatch.context() as mp:
            if not named:       # the forward rule as the parent had it
                mp.setattr(kda, "_kept", lambda x: x)
            return jax.jit(grads).lower(*shapes).as_text()

    named = lowered(True)
    snap = counters.snapshot()
    assert snap["kda_chunk.pallas"] == 1 and COUNTER not in snap
    assert named == lowered(False)
