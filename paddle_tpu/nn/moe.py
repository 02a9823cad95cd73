"""Mixture-of-Experts layers. Two of them, for two routing contracts:

- :class:`SparseMoELayer` is the DROPLESS layer: sigmoid or softmax
  scores over all experts, top-k of any k, renormalised and scaled
  weights, an optional shared expert; experts gated, ``(silu(x G) * x U)
  D``, or with ``w_gate`` absent plain, ``relu(x U)^2 D``. It is told
  which experts it holds
  (``experts_held`` from ``expert_offset``), routes over all of them and
  computes its own experts' part; no token is ever dropped and there is
  no capacity factor. Sorted (token, expert) pairs go through a grouped
  matrix product on a rung of a ladder of row capacities that the count
  of pairs picks: ``jax.lax.ragged_dot``, whose time follows the live
  rows, or on a TPU the Pallas launches of ``ops.pallas.grouped_ffn``,
  the same function on a static row count, which compute the 512-row
  tiles of a rung that hold a pair. The top rung of a share that
  holds at most twice the experts a token picks is every token through
  every held expert, as ONE FFN of width held x F with the routing
  weights on its hidden activations (``_every_pair_ffn``): three large
  products for gated experts, two for plain ones, no loop over experts,
  a step's time independent of its routing. Under it a sorted rung
  stays only where it costs less (``_row_ladder``, in dense rows from
  the shapes): ``ragged_dot``'s at a third of the dense rows or fewer,
  else the kernels' where their launches, gathers and way back come to
  less than the dense rung; a share whose sorted rungs would cost more
  runs the dense rung alone.
- :class:`MoELayer` is the CAPACITY layer: GShard top-2 softmax gating
  into a static ``(tokens, experts, capacity)`` grid that drops what
  overflows, with the explicit expert-parallel exchange below.

The rest of this docstring is about the capacity layer.

Mixture-of-Experts with REAL expert parallelism (the "ep" mesh axis).

The reference framework predates MoE entirely (SURVEY §2.6: EP absent) —
this is a TPU-first design, not a port. Tokens are routed top-2 by a
learned gate with a GShard/Switch-style static capacity (overflow tokens
drop to the residual path, keeping every shape static for XLA).

ISSUE 19 makes the token exchange EXPLICIT. The previous design ran
only the expert FFN inside ``shard_map`` and let GSPMD insert whatever
resharding collectives it liked at the boundary; now the whole
dispatch/combine runs inside ``shard_map`` (tokens sharded over "ep",
expert-stacked weights sharded over "ep") with two hand-placed
``lax.all_to_all`` exchanges:

- dispatch: each device scatters its LOCAL tokens into the full
  (e, c, d) capacity grid (zeros elsewhere — capacity slots are
  globally unique, so contributions are disjoint), splits it by
  destination device and all-to-alls; summing the received per-source
  blocks yields this device's experts' complete inputs. Disjoint + 0/1
  dispatch weights means the sum adds exact zeros: the explicit path
  is numerically the dense path.
- combine: the FFN outputs tile n ways and all-to-all back, giving
  every device the full (e, c, d) expert outputs for its local
  combine einsum.

Gating stays GLOBAL (logits all-gather over "ep" — (t, e), tiny):
capacity positions come from a global running count, so routing — and
therefore the math — is IDENTICAL to the single-device gate, which is
the parity oracle the tests pin. Dispatch payloads can optionally ride
int8 (``dispatch_codec="int8"``, the PR 15 wire codec) with a
straight-through estimator so gradients flow unquantized; that leg is
accuracy-gated by the caller exactly like the int8 ring.

The ``moe_a2a.*`` dispatch counters record which path served each
apply (explicit / the legacy GSPMD-resharding shard_map / dense) with
the refusal reason; ``PADDLE_MOE_A2A=0`` pins the legacy path.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, PartitionSpec

from ..framework import nan_inf
from ..framework.op import primitive
from .layer import Layer

__all__ = ["MoELayer", "SparseMoELayer", "sparse_moe", "moe_apply_ep",
           "MOE_EP_RULES", "top2_gating", "moe_route_stats",
           "moe_a2a_nbytes"]

# parameter sharding rules: expert-stacked weights shard over "ep"
MOE_EP_RULES = [
    (r".*experts_w1$", PartitionSpec("ep", None, None)),
    (r".*experts_b1$", PartitionSpec("ep", None)),
    (r".*experts_w2$", PartitionSpec("ep", None, None)),
    (r".*experts_b2$", PartitionSpec("ep", None)),
]


def moe_a2a_escaped() -> bool:
    """True when ``PADDLE_MOE_A2A=0`` pins the legacy GSPMD-resharding
    path (the bitwise escape for the explicit exchange)."""
    return os.environ.get("PADDLE_MOE_A2A", "").strip() in (
        "0", "off", "false")


def top2_gating(logits, capacity: int):
    """GShard top-2 gating with static capacity.

    logits: (tokens, experts). Returns (dispatch (t, e, c) bool,
    combine (t, e, c) float) — dispatch scatters tokens into expert
    capacity slots, combine holds the normalized gate weights.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    g1_idx = jnp.argmax(probs, axis=-1)                     # (t,)
    g1 = jnp.take_along_axis(probs, g1_idx[:, None], 1)[:, 0]
    probs2 = probs.at[jnp.arange(t), g1_idx].set(0.0)
    g2_idx = jnp.argmax(probs2, axis=-1)
    g2 = jnp.take_along_axis(probs2, g2_idx[:, None], 1)[:, 0]
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    def slots_for(idx):
        # position of each token within its expert's queue (running count)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)    # (t, e)
        pos = jnp.cumsum(onehot, axis=0) - onehot           # tokens before
        return jnp.sum(pos * onehot, axis=-1)               # (t,)

    pos1 = slots_for(g1_idx)
    # second choice queues behind all first choices of that expert
    count1 = jnp.sum(jax.nn.one_hot(g1_idx, e, dtype=jnp.int32), axis=0)
    pos2 = slots_for(g2_idx) + count1[g2_idx]

    def scatter(idx, pos):
        keep = pos < capacity
        d = (jax.nn.one_hot(idx, e, dtype=jnp.float32)[:, :, None] *
             jax.nn.one_hot(jnp.where(keep, pos, 0), capacity,
                            dtype=jnp.float32)[:, None, :])
        d = d * keep[:, None, None]
        return d

    d1 = scatter(g1_idx, pos1)
    d2 = scatter(g2_idx, pos2)
    combine = d1 * g1[:, None, None] + d2 * g2[:, None, None]
    dispatch = (d1 + d2) > 0
    # load-balancing auxiliary loss (GShard eq.4): mean prob * mean assignment
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(g1_idx, e, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * e
    return dispatch, combine, aux


def moe_route_stats(logits, capacity: int):
    """Routing diagnostics for one gate evaluation (dump_passes --moe
    and the bench probe): per-expert assigned token-choice counts
    (capacity-kept), per-expert overflow drops, and the overall
    capacity drop percentage of the 2t token-choices."""
    dispatch, _combine, aux = top2_gating(logits, capacity)
    t, e = logits.shape
    kept = jnp.sum(dispatch, axis=(0, 2))                   # (e,)
    probs = jax.nn.softmax(logits, axis=-1)
    g1 = jnp.argmax(probs, axis=-1)
    p2 = probs.at[jnp.arange(t), g1].set(0.0)
    g2 = jnp.argmax(p2, axis=-1)
    wanted = (jnp.sum(jax.nn.one_hot(g1, e), axis=0)
              + jnp.sum(jax.nn.one_hot(g2, e), axis=0))     # (e,)
    dropped = wanted - kept
    total = 2.0 * t
    return {
        "experts": int(e), "capacity": int(capacity),
        "tokens": int(t),
        "kept_per_expert": [int(v) for v in kept],
        "dropped_per_expert": [int(v) for v in dropped],
        "drop_pct": round(100.0 * float(jnp.sum(dropped)) / total, 2),
        "aux_loss": float(aux),
    }


def moe_a2a_nbytes(e: int, capacity: int, d: int, group: int,
                   codec: Optional[str] = None) -> int:
    """Per-device wire bytes of the two explicit all-to-alls (dispatch
    + combine): each moves ``(g-1)/g`` of the (e, c, d) capacity grid
    off-device. int8 dispatch payloads shrink that leg to 1 byte/elem
    + one f32 scale per d-row; the combine leg always rides f32
    (update results come back exact, like the ZeRO gather)."""
    g = max(1, int(group))
    if g <= 1:
        return 0
    elems = int(e) * int(capacity) * int(d)
    off = (g - 1)
    per_dev = elems // g
    if codec == "int8":
        dispatch = per_dev * (1 + 4 / int(d))
    else:
        dispatch = per_dev * 4
    combine = per_dev * 4
    return int(off * (dispatch + combine))


def _expert_ffn(w1, b1, w2, b2, x):
    """One expert's FFN on its capacity block: x (c, d)."""
    return jax.nn.gelu(x @ w1 + b1) @ w2 + b2


def _moe_dense(params, x, capacity):
    """The single-device oracle: global gate, dense vmap over ALL
    experts. The explicit EP path must match this (tolerance-gated
    when dispatch payloads quantize)."""
    logits = x @ params["gate_w"]
    dispatch, combine, aux = top2_gating(logits, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    out_e = jax.vmap(_expert_ffn)(
        params["experts_w1"], params["experts_b1"],
        params["experts_w2"], params["experts_b2"], expert_in)
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), out_e)
    return out, aux


def _st_quant(flat, block):
    """int8 round-trip with a straight-through estimator: forward is
    the decoded payload (what the wire delivers), gradient is identity
    (the router/gate must keep learning through the exchange)."""
    from ..parallel.collectives import quant_decode, quant_encode

    q, sc = quant_encode(flat, "int8", block=block)
    dec = quant_decode(q, sc, "int8", block=block)
    return flat + jax.lax.stop_gradient(dec - flat)


def _moe_explicit_a2a(params, x, mesh, axis, n, capacity, codec):
    """The explicit expert-parallel exchange (module docstring): global
    gate on all-gathered logits, local scatter, all_to_all dispatch,
    local-expert FFN, all_to_all combine."""
    from ..parallel.collectives import shard_map_nocheck

    e = params["experts_w1"].shape[0]
    t, d = x.shape
    t_l, e_l = t // n, e // n

    def local(x_loc, gate_w, w1, b1, w2, b2):
        # global gating: every device computes the SAME dispatch plan
        # from the full token set (the (t, e) logits gather is the
        # cheap exchange; capacity positions need the global running
        # count to match the single-device oracle)
        x_full = jax.lax.all_gather(x_loc, axis, axis=0, tiled=True)
        dispatch, combine, aux = top2_gating(x_full @ gate_w, capacity)
        r = jax.lax.axis_index(axis)
        disp_loc = jax.lax.dynamic_slice_in_dim(
            dispatch.astype(x_loc.dtype), r * t_l, t_l, 0)
        comb_loc = jax.lax.dynamic_slice_in_dim(
            combine.astype(x_loc.dtype), r * t_l, t_l, 0)
        # local scatter into the FULL capacity grid: zeros except this
        # device's tokens' slots (globally unique -> disjoint)
        ein = jnp.einsum("tec,td->ecd", disp_loc, x_loc)
        payload = ein.reshape(n * e_l, capacity, d)
        if codec == "int8":
            payload = _st_quant(payload.reshape(-1), d).reshape(
                payload.shape)
        # dispatch a2a: block j of the result is device j's partial
        # contribution for THIS device's experts; the sum completes
        # the disjoint scatter
        recv = jax.lax.all_to_all(payload, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        ein_loc = jnp.sum(recv.reshape(n, e_l, capacity, d), axis=0)
        out_loc = jax.vmap(_expert_ffn)(w1, b1, w2, b2, ein_loc)
        # combine a2a: tile n ways so every device assembles the full
        # (e, c, d) expert outputs for its local combine
        full = jax.lax.all_to_all(
            jnp.tile(out_loc, (n, 1, 1)), axis, split_axis=0,
            concat_axis=0, tiled=True)
        out = jnp.einsum("tec,ecd->td", comb_loc,
                         full.reshape(e, capacity, d))
        return out, aux

    spec_t = PartitionSpec(axis, None)
    spec_e1 = PartitionSpec(axis, None)
    spec_e2 = PartitionSpec(axis, None, None)
    return shard_map_nocheck(
        local, mesh,
        (spec_t, PartitionSpec(), spec_e2, spec_e1, spec_e2, spec_e1),
        (spec_t, PartitionSpec()),
    )(x, params["gate_w"], params["experts_w1"], params["experts_b1"],
      params["experts_w2"], params["experts_b2"])


def moe_apply_ep(params, x, *, mesh: Optional[Mesh] = None, axis: str = "ep",
                 capacity_factor: float = 2.0,
                 dispatch_codec: Optional[str] = None):
    """Expert-parallel MoE apply.

    params: dict with gate_w (d, E), experts_w1 (E, d, h), experts_b1
    (E, h), experts_w2 (E, h, d), experts_b2 (E, d). x: (tokens, d)
    global. Experts shard over `axis`; tokens all_to_all to their
    experts and back (explicit exchange — see the module docstring).
    ``dispatch_codec="int8"`` quantizes the dispatch payload on the
    wire (straight-through gradients). Falls back to the legacy
    GSPMD-resharding shard_map when the explicit path is ineligible,
    and to the dense einsum path when the mesh axis is unusable; every
    path lands a ``moe_a2a.*`` counter.
    """
    from ..ops.pallas.counters import bump

    e = params["experts_w1"].shape[0]
    t, d = x.shape
    capacity = max(1, int(capacity_factor * t / e))

    if mesh is None or axis not in mesh.axis_names or \
            mesh.shape[axis] <= 1 or e % mesh.shape[axis] != 0:
        bump("moe_a2a", "xla",
             "dense path: no usable mesh axis "
             f"(mesh={None if mesh is None else dict(mesh.shape)}, "
             f"axis={axis!r}, experts={e})")
        return _moe_dense(params, x, capacity)

    n = mesh.shape[axis]
    if not moe_a2a_escaped() and t % n == 0:
        out, aux = _moe_explicit_a2a(params, x, mesh, axis, n, capacity,
                                     dispatch_codec)
        bump("moe_a2a", "a2a")
        return out, aux
    bump("moe_a2a", "xla",
         "legacy GSPMD resharding: "
         + ("escaped (PADDLE_MOE_A2A=0)" if moe_a2a_escaped()
            else f"tokens={t} not divisible by {axis}={n}"))

    logits = x @ params["gate_w"]
    dispatch, combine, aux = top2_gating(logits, capacity)
    # gather expert inputs: (e, c, d)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)

    def local(w1, b1, w2, b2, ein):
        # ein arrives (e/n, c, d) after the spec split: this rank's
        # experts' tokens. (XLA inserts the all_to_all when the
        # upstream einsum output resharded from token- to expert-
        # sharded layout.)
        return jax.vmap(_expert_ffn)(w1, b1, w2, b2, ein)

    spec_e = PartitionSpec(axis)
    out_e = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_e, spec_e, spec_e, spec_e, spec_e),
        out_specs=spec_e,
    )(params["experts_w1"], params["experts_b1"],
      params["experts_w2"], params["experts_b2"], expert_in)
    # combine back to tokens
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), out_e)
    return out, aux


@primitive("moe")
def _moe_prim(xf, gate_w, w1, b1, w2, b2, mesh=None, capacity_factor=2.0,
              dispatch_codec=None):
    params = {"gate_w": gate_w, "experts_w1": w1, "experts_b1": b1,
              "experts_w2": w2, "experts_b2": b2}
    return moe_apply_ep(params, xf, mesh=mesh,
                        capacity_factor=capacity_factor,
                        dispatch_codec=dispatch_codec)


class MoELayer(Layer):
    """Transformer FFN replaced by num_experts expert FFNs + top-2 gate."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 capacity_factor: float = 2.0, dispatch_codec=None,
                 name=None):
        super().__init__()
        from .initializer import XavierUniform

        self.d_model = d_model
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.dispatch_codec = dispatch_codec
        init = XavierUniform()
        self.gate_w = self.create_parameter(
            [d_model, num_experts], default_initializer=init)
        self.experts_w1 = self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=init)
        self.experts_b1 = self.create_parameter(
            [num_experts, d_hidden], is_bias=True)
        self.experts_w2 = self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=init)
        self.experts_b2 = self.create_parameter(
            [num_experts, d_model], is_bias=True)
        self._last_aux_loss = None

    def forward(self, x):
        from .. import ops
        from ..parallel.mesh import get_mesh

        shape = x.shape
        xf = ops.reshape(x, [-1, shape[-1]])
        out, aux = _moe_prim(xf, self.gate_w, self.experts_w1,
                             self.experts_b1, self.experts_w2,
                             self.experts_b2, mesh=get_mesh(),
                             capacity_factor=self.capacity_factor,
                             dispatch_codec=self.dispatch_codec)
        self._last_aux_loss = aux
        return ops.reshape(out, list(shape))

    @property
    def aux_loss(self):
        return self._last_aux_loss


# ---------------------------------------------------------------------------
# the dropless layer
# ---------------------------------------------------------------------------
#: the multiply-adds a v5e's MXU does, at the dense rung's rate, while XLA
#: gathers one ROW, whatever its width (62 ns: 48 the gather, the rest
#: the row's scalars), and while it sorts and indexes one (token, pick)
#: SLOT of a share (40 ns): a device trace of one layer's step and the
#: three cells that ran the kernels (PERF.md sections 5 and 6, PR 47;
#: ``tools/op_bench.py --ops expert_ffn`` prints a rung's time beside
#: what these state)
GROUPED_GATHER, GROUPED_SLOT = 5e6, 3.2e6
#: what the kernels' rungs call the sort of a layer's pairs, both ways
KEPT = "sparse_moe_sort"


def _grouped_cost(tokens: int, top_k: int, held: int, d: int, f: int,
                  gated: bool):
    """What a rung costs through ``ops.pallas.grouped_ffn`` on experts of
    D x F, in dense rows (one token through one expert on the dense rung,
    forward, recomputed and backward: seven products of D x F plain,
    eleven gated): ``(a row's, a rung's beside its rows)``. A launched
    row's products are a dense row's (the launches run at the MXU's rate,
    as the dense rung's do) and are paid by the LIVE tile only: the
    launches stop at their last live tile, so this term states a rung
    that is full, and overstates one that is not (a corrected model may
    admit the narrow experts' shares: ROADMAP S12); it is gathered three
    times, the token's row forward and recomputed and its cotangent,
    whether it holds a pair or not; and the way back, forward and
    backward, is three passes of a one-hot product of BLOCK tokens for
    every CHUNK of rows that holds a pair. Beside its rows a rung
    launches a tile more for each expert, its way back visits a chunk
    more for every (block of tokens, expert), and the sort, its inverse
    and the rows' layout walk every slot of the share, whatever the rung
    holds. So narrow experts' rungs are dear."""
    from ..ops.pallas.grouped_ffn import BLOCK, CHUNK, TILE

    unit = (11 if gated else 7) * d * f         # a dense row's multiply-adds
    row = 1.0 + 3 * GROUPED_GATHER / unit + 2 * 3 * BLOCK * d / unit
    visits = held * -(-tokens // BLOCK)
    return row, (row * held * TILE + tokens * top_k * GROUPED_SLOT / unit
                 + 2 * 3 * visits * CHUNK * BLOCK * d / unit)


def _row_ladder(pairs: int, experts_held: int, num_experts: int,
                dense_rows: int = 0, row_cost: float = 3,
                skew: int = 8, rung_cost: float = 0) -> tuple:
    """The static row capacities the grouped product may run at,
    ascending, in whole 256-row blocks. The top rung holds every (token,
    expert) pair there can be, so nothing is ever dropped; the lowest is
    ``skew`` times what even routing would send to the experts held, and
    each rung is four times the one below: uneven routing (an expert's
    load follows its tokens' frequencies, and a share's experts, the
    only ones whose output reaches the loss, draw 5.4-5.7 times the even
    share by the end of a benchmark window) then moves the rung seldom,
    at the price of rows that hold no pair: ``ragged_dot``'s products
    and the kernels' take as long as the live rows, a tile of 512 at a
    time in the kernels, so a step's time follows its routing smoothly
    (1% of a rung a tile) and jumps only where a count crosses a rung;
    the gathers, the sort and the grids' steps are paid by the rung.
    Where the top rung is the dense one, every token through every held
    expert on ``dense_rows`` rows, a sorted rung stays only where it
    costs no more than those: ``row_cost`` dense rows a row and
    ``rung_cost`` beside them. The defaults are ``ragged_dot``'s: a
    sorted row costs what 2.5 to 3.3 dense rows cost (its gather, its
    selects and its float32 scatter-add, PERF.md section 7.10), so above
    a third it is no cheaper than the dense rung. The grouped kernels'
    are ``_grouped_cost``; under a dense top they may keep a rung of
    every pair. All of it follows from the shapes."""
    def blocks(rows):
        return -(-int(rows) // 256) * 256

    top = blocks(pairs)
    rung = blocks(-(-skew * pairs * experts_held // num_experts))
    rungs = []
    while (rung <= top and row_cost * rung + rung_cost <= dense_rows) \
            if dense_rows else rung < top:
        rungs.append(rung)
        rung *= 4
    return tuple(rungs) + (top,)


def _hidden(gate, up, of):
    """An expert's hidden activations: gated, ``silu(of(gate)) *
    of(up)``, or with no gate plain, ``relu(of(up))^2``. ``of`` makes the
    operand a rung computes on out of what it holds."""
    if gate is None:
        return jnp.square(jax.nn.relu(of(up)))
    return jax.nn.silu(of(gate)) * of(up)


def _stored_layout(w, device=None):
    """The order of dimensions in which the device keeps an array of
    ``w``'s shape and type, as a :class:`Layout`: not always the array's
    own (a v5e keeps a float32 (8, 2688, 1856) stack with the 2688 minor,
    1856 not being whole lanes of 128, and a (16, 2304, 896) one as it
    is)."""
    device = jax.devices()[0] if device is None else device
    return Layout(major_to_minor=Layout.from_pjrt_layout(
        device.client.get_default_layout(w.dtype, w.shape, device)
    ).major_to_minor)


def _every_pair_ffn(x, weight_by_expert, w_gate, w_up, w_down, stored):
    """The top rung: every token through every expert held, as ONE FFN
    of width H x F, gated or (``w_gate`` None) plain. ``weight_by_expert``
    (T, H) is zero where the
    token did not pick the expert; it scales the hidden activations (in
    float32, before they are rounded to the products' type), so the down
    product contracts expert and width together and the sum over experts
    happens in its float32 accumulator. The same rows as sorting all
    T x H pairs would give the grouped product, without their T x H x D
    gathered copy; no loop over experts, so nothing is stacked for the
    backward and a recomputed forward stops at the hidden activations."""
    # pinned to ``stored``, the layout the device keeps them in, for
    # their cotangents' sake: XLA forms each weight gradient as (H, F, D)
    # and, left free where that is not how the weight is kept, runs AdamW
    # in that layout on transposed copies of the weight and both its
    # moments, in and out
    w_gate, w_up = (w if w is None else with_layout_constraint(w, stored)
                    for w in (w_gate, w_up))
    # the rows in the products' type once, and not once for each tile of
    # both products inside their fusions (6.9 against 5.6 ms a product at
    # 16,384 x 2304 x 14,336 on a v5e, PERF.md section 6, PR 32)
    rows = jax.lax.optimization_barrier(x.astype(w_up.dtype))

    # recomputed in the backward from the products' own results: as
    # a branch of the ladder's switch the rung would else hand its
    # float32 intermediates over the branch's boundary as residuals
    @nan_inf.checkpoint
    def weighted_hidden(gate, up, weight):
        hidden = _hidden(gate, up, lambda a: a.astype(jnp.float32))
        return nan_inf.probe(
            "hidden", (hidden * weight[:, :, None]).astype(w_down.dtype))

    hidden = weighted_hidden(
        None if w_gate is None else jnp.einsum("td,hdf->thf", rows, w_gate),
        jnp.einsum("td,hdf->thf", rows, w_up), weight_by_expert)
    return jnp.einsum("thf,hfd->td", hidden, w_down,
                      preferred_element_type=jnp.float32)


def _grouped_ffn(x, tokens, weights, sizes, w_gate, w_up, w_down, n_tokens):
    """FFN of each expert, gated or (``w_gate`` None) plain, on its own
    run of the sorted rows, then
    the weighted scatter-add back to the tokens. What ``ragged_dot``
    leaves in the rows past the last run is not specified (zeros on the
    CPU, not on the TPU), forward or transposed: those rows are selected
    away after every product, so that neither they nor their cotangents
    reach a token."""
    live = (jnp.arange(tokens.shape[0]) < jnp.sum(sizes))[:, None]

    def runs(a):
        return jnp.where(live, a, jnp.zeros((), a.dtype))

    rows = runs(x[tokens].astype(w_up.dtype))
    hidden = nan_inf.probe("hidden", runs(_hidden(
        w_gate, w_up, lambda w: jax.lax.ragged_dot(rows, w, sizes))))
    out = runs(jax.lax.ragged_dot(hidden, w_down, sizes))
    out = out.astype(jnp.float32) * weights[:, None]
    return jnp.zeros((n_tokens, x.shape[-1]), jnp.float32).at[tokens].add(out)


#: the router's score of each expert from its logits, over ALL experts
SCORE_FUNCS = {"sigmoid": jax.nn.sigmoid,
               "softmax": lambda logits: jax.nn.softmax(logits, axis=-1)}


def _routed(x, router_w, router_bias, w_gate, w_up, w_down, *, top_k,
            expert_offset, scaling, renormalize, score_func, rungs,
            dense_top, grouped, dtype, stored):
    """``sparse_moe`` on the ladder it chose: ``rungs`` ascending, the top
    one the dense rung (``dense_top``) or sorted as the others; the sorted
    rungs through the kernels (``grouped``) or ``ragged_dot``; products in
    ``dtype`` (None: the stacks' own); ``stored`` the layout the device
    keeps the gate / up stacks in."""
    from ..ops.pallas import grouped_ffn

    t = x.shape[0]
    held = w_up.shape[0]
    scores = SCORE_FUNCS[score_func](jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, picked = jax.lax.top_k(scores + router_bias, top_k)       # (T, k)
    weight = jnp.take_along_axis(scores, picked, axis=1)
    if renormalize:
        weight = weight / nan_inf.probe(
            "renorm_denominator", jnp.sum(weight, axis=1, keepdims=True),
            smallest=True)
    weight = (scaling * weight).reshape(-1)
    local = picked.reshape(-1) - expert_offset
    mine = (local >= 0) & (local < held)
    # a pair's expert here, or ``held`` for an expert that is elsewhere
    slot = jnp.where(mine, local, held)
    # pairs on held experts first, expert by expert (a stable sort keeps
    # the tokens of an expert in order)
    order = jnp.argsort(slot, stable=True)
    token = jnp.arange(t * top_k, dtype=jnp.int32) // top_k
    # (a one-hot sum, not a bincount: a TPU scatter walks its updates)
    one_hot = jax.nn.one_hot(slot, held + 1, dtype=jnp.int32)
    sizes = jnp.sum(one_hot, axis=0)[:held]
    count = jnp.sum(sizes)

    def cast(*ws):          # (plain experts' absent gate stays None)
        return tuple(w if dtype is None or w is None else w.astype(dtype)
                     for w in ws)

    if grouped:
        # the sort the other way round, without a scatter: a pair's row is
        # its expert's first row plus the earlier pairs on that expert
        earlier = jnp.cumsum(one_hot, axis=0) - one_hot
        first = jnp.cumsum(sizes) - sizes
        row_of_slot = jnp.where(mine, jnp.sum(
            one_hot[:, :held] * (earlier[:, :held] + first), axis=1), -1)
        # (both ways kept across a block's recomputation, 0.8 MB a layer:
        # ``optimizer.meta.recompute``; an identity anywhere else)
        order, row_of_slot = (checkpoint_name(a, KEPT)
                              for a in (order, row_of_slot))
    else:
        w_gate, w_up, w_down = cast(w_gate, w_up, w_down)

    def at(rows):
        def run(x, weight, w_gate, w_up, w_down):
            take = order[:rows]
            if grouped:     # the stacks as stored: it rounds them itself
                # (a pair past the rung's rows reads as one with no row)
                return grouped_ffn.grouped_ffn(
                    x, weight.reshape(t, top_k), take,
                    row_of_slot.reshape(t, top_k), sizes, w_gate, w_up,
                    w_down, dtype=dtype, declared=True,
                    up_minor_d=stored.major_to_minor == (0, 2, 1))
            return _grouped_ffn(
                x, token[take], jnp.where(mine[take], weight[take], 0.0),
                sizes, w_gate, w_up, w_down, t)
        return run

    def every_pair(x, weight, w_gate, w_up, w_down):
        # each token's weight on each held expert: its picks compared
        # with the experts and summed (no scatter, see ``sizes``); a pick
        # that is elsewhere (slot ``held``) matches none
        by_expert = jnp.sum(jnp.where(
            slot.reshape(t, top_k, 1) == jnp.arange(held),
            weight.reshape(t, top_k, 1), 0.0), axis=1)
        return _every_pair_ffn(x, by_expert, w_gate, w_up, w_down, stored)

    top = every_pair if dense_top else at(rungs[-1])
    if grouped and dense_top:
        # the stacks reach the switch as stored; the dense rung rounds
        # them first. Under grouped rungs it recomputes what it would keep
        # for its backward (the gate and up results, T x H x F each):
        # they would be residuals of EVERY branch of the switch, written
        # as zeros by the grouped rung that runs instead (0.87 ms each at
        # 16,384 x 8 x 1856, PERF.md section 6, PR 47); it keeps the
        # rounded stacks
        inner = nan_inf.checkpoint(every_pair)

        def top(x, weight, w_gate, w_up, w_down):
            return inner(x, weight, *cast(w_gate, w_up, w_down))
    rung = jnp.sum(count > jnp.asarray(rungs[:-1], jnp.int32))
    # (lax.switch; in a step built under FLAGS_check_nan_inf it also hands
    # out the ``hidden`` row of the rung that ran)
    out = nan_inf.probe("routed", nan_inf.switch(
        rung, [at(r) for r in rungs[:-1]] + [top],
        x, weight, w_gate, w_up, w_down))
    # the dense top rung's rows, in the ladder's whole 256-row blocks
    rows = rungs[:-1] + ((-(-t * held // 256) * 256,) if dense_top
                         else rungs[-1:])
    ran = jnp.asarray(rows, jnp.float32)[rung]
    return out, jnp.stack([count.astype(jnp.float32), ran])


#: ``_routed`` traced once for a step's expert layers, which have one shape:
#: tracing and differentiating the kernels' rungs layer by layer (the padded
#: layout's index arithmetic, two branches of a switch, a ``custom_vjp``)
#: cost four expert layers 1.7 s of ``setup_s`` on the chip's host where
#: the dense rung alone costs 0.4 (PERF.md section 6, PR 47); XLA inlines
#: the call
_routed_once = jax.jit(_routed, static_argnames=(
    "top_k", "expert_offset", "scaling", "renormalize", "score_func",
    "rungs", "dense_top", "grouped", "dtype", "stored"))


@primitive("sparse_moe")
def sparse_moe(x, router_w, router_bias, w_gate, w_up, w_down, top_k,
               expert_offset=0, scaling=1.0, renormalize=True,
               score_func="sigmoid"):
    """The routed part of the dropless layer on tokens ``x`` (T, D).

    ``router_w`` (D, E) scores ALL E experts; ``w_gate`` / ``w_up``
    (H, D, F) and ``w_down`` (H, F, D) are the H experts held here,
    experts ``expert_offset`` .. ``expert_offset + H - 1``: gated,
    ``(silu(x G) * x U) D``, or with ``w_gate`` None plain,
    ``relu(x U)^2 D``. Returns the
    sum over the picked AND held experts of weight * expert(x), float32,
    and ``[pairs on held experts, rows of the rung that ran]``. Scores are
    ``score_func`` (:data:`SCORE_FUNCS`) of the logits: ``sigmoid`` scores
    each expert alone, ``softmax`` over all E. The
    renormalisation is over all ``top_k`` picks, held or not; the pick
    itself passes no gradient. Routing is float32 at full precision
    whatever the autocast level (a rounded score flips picks); the
    experts' products run in the autocast type."""
    from ..amp import amp_dtype, amp_enabled
    from ..ops.pallas import grouped_ffn
    from ..ops.pallas.counters import bump

    (t, d), (held, _, f) = x.shape, w_up.shape
    num_experts = router_w.shape[1]
    # The top rung. With no more experts here than a token picks, every
    # pair is every token through every expert: no sort, no gathered copy
    # of the rows, one FFN of width H x F. Up to twice as many
    # experts as picks it still is the top rung, on T x H rows where the
    # sorted pairs would be T x k: the TPU's ragged_dot takes as long as
    # its LIVE rows, so a step's time on the sorted rung follows its
    # routing (19,243-21,373 tokens/s over twelve seeds of the Mellum
    # cell, 16 held of top 8, PERF.md section 6, PR 31), and the sort, the
    # gather and the scatter of T x k rows cost more than the products
    # they feed. For the same two reasons ``_row_ladder`` keeps a sorted
    # rung under the dense one only at a third of its rows or fewer
    # (19,385-20,097 tokens/s over twelve seeds of the Nemotron cell on
    # a sorted rung of 0.375 of them, section 6, PR 33).
    dense_top = held <= 2 * top_k
    pairs = t * min(top_k, held)
    dense_rows = t * held if dense_top else 0
    rungs = _row_ladder(pairs, held, num_experts, dense_rows)
    # On a TPU the sorted rungs may be Pallas grouped products on a static
    # row count, which compute the tiles of a rung that hold a pair and
    # neither select nor scatter; ``ragged_dot`` is the same function's
    # plain statement. Under a dense top they take the work the DENSE rung
    # does today, where ``_row_ladder`` finds a rung of theirs that costs
    # less than it (``_grouped_cost``, from the shapes: the wide experts'
    # shares, Nemotron's and LFM2's, and not the narrow ones', Kanana's,
    # which lost 1.5% on them, and Mellum's). A
    # share that ``ragged_dot``'s own ladder serves keeps it: that rung is
    # a third of the dense rows or fewer and mostly empty (1-3 thousand
    # live pairs of 16,384 in the Kimi cell), and its time follows its
    # live rows, which no static rung matches (-5.3% there on the kernels,
    # PERF.md section 6, PR 47).
    grouped = grouped_ffn.takes() and (len(rungs) == 1 or not dense_top)
    if grouped and dense_top:
        row, rung = _grouped_cost(t, top_k, held, d, f, w_gate is not None)
        rungs = _row_ladder(pairs, held, num_experts, dense_rows, row,
                            rung_cost=rung)
        grouped = len(rungs) > 1
    if grouped and dense_top and rungs[-2] >= rungs[-1]:
        # the grouped rung holds every pair there can be: the dense rung
        # above it could never run, and is not built
        rungs, dense_top = rungs[:-1], False
    dtype = amp_dtype() if amp_enabled() else None
    bump("sparse_moe", "every_pair" if dense_top else "sorted")
    bump("sparse_moe", "plain" if w_gate is None else "gated")
    if grouped:
        bump("sparse_moe", "grouped")
        for rows in rungs[:len(rungs) - dense_top]:
            grouped_ffn.declare(rows, t, held, d, f, w_gate is not None,
                                w_up.dtype if dtype is None else dtype)
    # (a step built under FLAGS_check_nan_inf leaves its rows as it traces)
    run = _routed_once if grouped and nan_inf.record is None else _routed
    return run(x, router_w, router_bias, w_gate, w_up, w_down, top_k=top_k,
               expert_offset=expert_offset, scaling=scaling,
               renormalize=renormalize, score_func=score_func, rungs=rungs,
               dense_top=dense_top, grouped=grouped, dtype=dtype,
               stored=_stored_layout(w_up))     # of the stack as it is kept


class SparseMoELayer(Layer):
    """Dropless top-k mixture of experts (see the module docstring).

    ``experts_held`` of the ``num_experts`` experts live here, from
    ``expert_offset``; with all of them held this is the whole layer. A
    share's output leaves out what the absent experts would have added;
    ``shared_width`` adds one always-on expert, of that width and the
    routed experts' form, that every share computes alike; with
    ``shared_gate`` its output is multiplied by ``sigmoid(x w_s)``, ``w_s``
    a ``(d_model, 1)`` leaf without bias (counter ``moe.shared_gate`` once
    a build). ``gated``
    experts are ``(silu(x G) * x U) D`` (``nn.GatedFFN``), the others
    plain, ``relu(x U)^2 D`` (``nn.PlainFFN``) with no ``experts_gate``.
    ``forward`` keeps ``[pairs on
    held experts, rows of the rung that ran]`` of its last call in
    ``last_routing``."""

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 experts_held=None, expert_offset=0, scaling=1.0,
                 renormalize=True, shared_width=None, score_func="sigmoid",
                 gated=True, shared_gate=False):
        super().__init__()
        from ..ops.pallas.counters import bump
        from .common import GatedFFN, Linear, PlainFFN

        held = num_experts if experts_held is None else int(experts_held)
        if not 0 <= expert_offset <= num_experts - held:
            raise ValueError(
                f"experts {expert_offset}..{expert_offset + held - 1} are "
                f"not among {num_experts}")
        if score_func not in SCORE_FUNCS:
            raise ValueError(f"router scores {score_func!r}: "
                             f"{sorted(SCORE_FUNCS)} are built")
        self.score_func = score_func
        self.top_k, self.expert_offset = int(top_k), int(expert_offset)
        self.scaling, self.renormalize = float(scaling), bool(renormalize)
        self.router = Linear(d_model, num_experts, bias_attr=False)
        # the router's correction bias: a buffer, moved by a balancing
        # rule outside the loss and not by its gradient
        self.register_buffer("router_bias",
                             jnp.zeros((num_experts,), jnp.float32))
        self.experts_gate = self.create_parameter(
            [held, d_model, d_expert]) if gated else None
        self.experts_up = self.create_parameter([held, d_model, d_expert])
        self.experts_down = self.create_parameter([held, d_expert, d_model])
        self.shared = (GatedFFN if gated else PlainFFN)(
            d_model, shared_width) if shared_width else None
        if shared_gate and self.shared is None:
            raise ValueError("shared_gate on a layer with no shared expert")
        self.shared_gate = Linear(d_model, 1, bias_attr=False) \
            if shared_gate else None
        if shared_gate:
            bump("moe", "shared_gate")
        self.last_routing = None

    def forward(self, x):
        from .. import ops

        shape = x.shape
        routed, self.last_routing = sparse_moe(
            ops.reshape(x, [-1, shape[-1]]), self.router.weight,
            self.router_bias, self.experts_gate, self.experts_up,
            self.experts_down, top_k=self.top_k,
            expert_offset=self.expert_offset, scaling=self.scaling,
            renormalize=self.renormalize, score_func=self.score_func)
        out = ops.reshape(routed, list(shape))
        if self.shared is None:
            return out
        shared = self.shared(x)
        if self.shared_gate is not None:
            from . import functional as F

            shared = F.sigmoid(self.shared_gate(x)) * shared
        return out + shared
