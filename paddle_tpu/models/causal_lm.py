"""A decoder-only causal LM assembled from a model's own config keys.

``CausalLM.from_config(cfg)`` reads a Hugging Face style ``config.json``
dict and builds: token embedding, ``num_hidden_layers`` residual blocks,
a final RMSNorm and a head (its own matrix, or the embedding's where the
file says ``tie_word_embeddings``). A block is a token mixer AND a
feed-forward (``x += Mix(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``) or,
for a config that lays its layers out by ``hybrid_override_pattern``, ONE
sublayer behind one norm (``x += Sub(RMSNorm(x))``). Which kinds a block
gets is decided per layer from the config, through the small tables
below: a new architecture is a config file plus, at most, a new layer
kind registered there.

    layout  ``hybrid_override_pattern`` (one character a layer): ``M`` ->
            mixer ``mamba2``, ``*`` -> mixer ``gqa``, ``E`` -> ffn
            ``moe``, ``-`` -> ffn ``dense``; that layer's other sublayer
            is absent. Every other config: a mixer and an ffn a layer
    norms   two a block, before each sublayer; with ``sandwich_norm`` four:
            the sublayer's OUTPUT is normed too before it is added
            (``x += RMSNorm(Mix(RMSNorm(x)))``, the same round the ffn)
    passes  ``total_ut_steps`` T (default 1): the SAME blocks are walked T
            times a step, the final norm after each pass; its output is
            what the head and the exit gate read and what the next pass
            starts from (below)
    mixer   ``mamba2`` -> ``nn.Mamba2Mixer`` (``mamba_num_heads`` heads
            of ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``,
            ``conv_kernel``, ``time_step_*``)
            with ``layer_types``: ``conv`` -> ``conv``
            (``nn.GatedShortConv``: ``conv_L_cache`` taps; a file that
            says ``conv_bias`` true is refused by that key);
            ``sliding_attention`` / ``full_attention``
            -> ``gqa`` (``nn.GroupedQueryAttention``:
            ``num_key_value_heads`` key heads of ``head_dim``,
            ``sliding_window`` on the sliding layers, ``qk_norm``). Its
            positions: the layer type's entry of ``rope_parameters``, or
            for a file in the older spelling top-level ``rope_theta``
            (``rope_scaling`` has to be null); a file with neither key
            is refused, one that says ``rope_parameters: null`` has none.
            A file that states a relative-position term (``d_rel``), a
            short convolution (``use_sconv``) or a length-dependent
            scale (``log_scaling_alpha``) is refused by that key.
            A pattern's ``*`` is a full layer with no per-head norms and
            no positions unless the file gives a ``rope`` entry
            with ``full_attention_interval`` n (and the five
            ``linear_*`` keys; refused without ``linear_num_value_heads``):
            every n-th layer -> ``gqa``, the others -> ``gdn``
            (``nn.GatedDeltaNet``: ``linear_num_key_heads`` heads of
            ``linear_key_head_dim`` under ``linear_num_value_heads`` of
            ``linear_value_head_dim``, ``linear_conv_kernel_dim`` taps).
            Such a file's attention is a full layer with its positions
            from the top-level ``rope_theta``, on the first
            ``partial_rotary_factor`` of each head only where it has
            that key (a pattern's file keeps the key read by nothing:
            its attention takes ``rope`` alone), and with
            ``attn_output_gate`` a sigmoid gate from a doubled ``q_proj``
            on the attention's output
            else ``linear_attn_config.kda_layers`` (1-based) -> ``kda``
            (``nn.KimiDeltaAttention``); every other layer -> ``mla``
            (``nn.MLAttention``: NoPE where the file says
            ``mla_use_nope``, else its ``qk_rope_head_dim`` part rotated
            by ``rope_theta``, de-interleaved first where the file says
            ``rope_interleave``; ``q_lora_rank`` and ``rope_scaling``
            have to be null)
    ffn     with ``mlp_layer_types``: ``sparse`` -> ``moe``, ``dense`` ->
            ``dense``
            else the first ``first_k_dense_replace`` or
            ``num_dense_layers`` layers -> ``dense``;
            the others -> ``moe`` where the file counts experts
            (``num_experts`` or ``n_routed_experts``) and ``dense``
            where it counts none; a file with ``mlp_only_layers``
            (0-based) or ``decoder_sparse_step`` s: ``dense`` on the
            listed layers and on every layer that is no s-th one
            ``dense``: ``nn.GatedFFN`` of ``intermediate_size``
            (``hidden_act``, SiLU for a file without the key), or for a
            config that says
            ``mlp_hidden_act`` (``nemotron_h``) ``nn.PlainFFN``
            ``moe``: ``nn.SparseMoELayer``: ``num_experts_per_token`` or
            ``num_experts_per_tok`` of the router's ``num_experts`` or
            ``n_routed_experts``; ``num_shared_experts`` or
            ``n_shared_experts`` shared (of
            ``moe_shared_expert_intermediate_size`` where the file says
            it), or for a file with ``shared_expert_intermediate_size``
            ONE shared expert of that width under its sigmoid gate
            (``shared_gate``); experts gated, or plain relu^2 ones for
            a config that
            says ``mlp_hidden_act``; scores by
            ``moe_router_activation_func`` or ``scoring_func``, or
            softmax for a config that has neither key and says
            ``norm_topk_prob``, which is then the renormalisation (a
            file that says ``norm_topk_prob``, scores by sigmoid and has
            neither key has to gain one); ``use_expert_bias`` is the
            layer's ``router_bias``
            buffer, which every expert layer has, zero and outside the
            gradient
    head    ``tie_word_embeddings``: ONE ``(vocabulary, hidden)``
            parameter, ``embed.weight``, read by the embedding's gather
            and by the fused cross-entropy; its gradient is the sum of
            both uses and the model has no ``head`` leaf (counter
            ``causal_lm.tied_head`` once a build). Else a ``head`` matrix
            of its own

The norms' epsilon is ``rms_norm_eps``, ``layer_norm_epsilon`` or
``norm_eps``; a file with none of the three is refused. With
``zero_centered_norm`` every block norm, the final norm and the
attention's per-head norms scale by ``1 + w``, ``w`` started at zero
(``nn.RMSNorm(zero_centered=True)``; a linear mixer's output norm keeps
the ordinary scale).
A chip's share of an expert-parallel deployment is said with
``experts_held`` / ``expert_offset`` (the router keeps all its outputs).
``loss`` goes through the fused vocabulary cross-entropy, so the
``(tokens, vocabulary)`` logits never exist. ``recompute=True`` runs each
block again in the backward (``optimizer.meta.recompute``) and keeps of
it, for that: its input, its parameters, and the outputs of the flash
attention kernels inside it (the attention output and its logsumexp,
H x (2 dv + 4) bytes a token). The second run brings q, k, v back from
the projections; the attention kernel's forward launch, the one O(T^2)
piece of a block, would only write those two arrays again, so it runs
once a step and not twice.

**A looped model** (``total_ut_steps`` T > 1). ``h^0 = E[ids]``; pass t
walks all the blocks from ``h^{t-1}`` and ends in the final norm,
``h^t``. A block's parameters are used T times a step and their gradient
is the sum over the uses (each use a ``recompute`` segment of its own
with its own kept flash outputs). One untied head and one exit gate
``Linear(hidden, 1)`` read every pass: ``l^t_i`` the cross-entropy of
position i from ``h^t_i``, ``a^t_i`` the gate's logit, and the exit
distribution ``p^t_i = sigmoid(a^t_i) prod_{j<t} (1 - sigmoid(a^j_i))``
for t < T with the rest of the mass on the last pass. ``loss`` is the
expected loss under it with an entropy term (``exit_entropy_beta``, a
uniform prior over exits): ``mean_i [sum_t p^t_i l^t_i + beta sum_t
p^t_i log p^t_i]``, the distribution in float32 from log-sigmoids
(``F.expected_exit_loss``). The head runs ONCE, on the T passes' rows
stacked, through ``F.fused_linear_cross_entropy(reduction="none")``.
Training never exits early: every pass runs; ``forward`` gives the last
pass's logits. In the HLO pass t is under the scope ``ut_step<t>`` and
gate, head and loss under ``ut_exit_loss``; the counters
``causal_lm.ut_steps`` (T a build) and ``causal_lm.block_applications``
(T x L a traced step) say what ran.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..nn import functional as F
from ..nn.moe import SCORE_FUNCS


def _first(cfg, *names, default=None):
    """The value of the first of ``names`` the config has."""
    for name in names:
        if name in cfg:
            return cfg[name]
    return default


_EPS_KEYS = ("rms_norm_eps", "layer_norm_epsilon", "norm_eps")


def _zero_centered(cfg):
    return bool(cfg.get("zero_centered_norm", False))


def _eps(cfg):
    eps = _first(cfg, *_EPS_KEYS)
    if eps is None:
        raise ValueError(f"a config with none of {_EPS_KEYS}: the norms' "
                         "epsilon would be a guess")
    return eps


#: a ``hybrid_override_pattern`` character -> (mixer kind, ffn kind)
_PATTERN = {"M": ("mamba2", None), "*": ("gqa", None),
            "E": (None, "moe"), "-": (None, "dense")}


def _pattern_kinds(cfg, layer):
    char = cfg["hybrid_override_pattern"][layer - 1]
    if char not in _PATTERN:
        raise NotImplementedError(
            f"hybrid_override_pattern character {char!r} at layer {layer}: "
            f"{sorted(_PATTERN)} are built")
    return _PATTERN[char]


#: a ``layer_types`` entry -> the mixer kind that builds it
_LAYER_TYPES = {"conv": "conv", "sliding_attention": "gqa",
                "full_attention": "gqa"}


def _gqa_rope(cfg, kind):
    """The positions of a ``layer_types`` file's attention layer of
    ``kind``, as ``nn.GroupedQueryAttention`` takes them: the layer
    type's entry of ``rope_parameters`` (or the one entry for all), else
    the older spelling's top-level ``rope_theta``. None only where the
    file says ``rope_parameters: null``."""
    if "rope_parameters" in cfg:
        rope = cfg["rope_parameters"]
        if rope is not None and "rope_theta" not in rope:
            rope = rope[kind]               # one entry a layer type
        return rope
    if "rope_theta" in cfg:
        if cfg.get("rope_scaling") is not None:
            raise NotImplementedError(
                "rope_scaling beside a top-level rope_theta: say the scaled "
                "positions as a rope_parameters entry (rope_type yarn)")
        return {"rope_type": "default", "rope_theta": cfg["rope_theta"]}
    raise ValueError(
        "a layer_types config with neither rope_parameters nor rope_theta: "
        "its attention would be built without positions (say "
        "rope_parameters: null if it has none)")


def _mixer_gqa(cfg, layer):
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim", cfg["hidden_size"] // heads)
    rotary_dim = None
    if "hybrid_override_pattern" in cfg:
        # nemotron_h's attention: full, no per-head norms, and no
        # positions (the state-space layers carry them) unless the file
        # gives a ``rope`` entry; its ``partial_rotary_factor`` is read
        # by nothing
        kind, rope, qk_norm = ("full_attention", cfg.get("rope"),
                               cfg.get("qk_norm", False))
    else:
        kind = "full_attention" if "full_attention_interval" in cfg \
            else cfg["layer_types"][layer - 1]
        rope, qk_norm = _gqa_rope(cfg, kind), cfg.get("qk_norm", True)
        if "partial_rotary_factor" in cfg:
            if "rope_parameters" in cfg:
                raise NotImplementedError(
                    "partial_rotary_factor beside rope_parameters: which "
                    "of the two states the rotated part is not read")
            rotary_dim = int(cfg["partial_rotary_factor"] * head_dim)
    return nn.GroupedQueryAttention(
        cfg["hidden_size"], heads, cfg.get("num_key_value_heads", heads),
        head_dim,
        window=cfg["sliding_window"] if kind == "sliding_attention"
        else None,
        rope=rope, qk_norm=qk_norm, epsilon=_eps(cfg),
        output_gate=bool(cfg.get("attn_output_gate", False)),
        rotary_dim=rotary_dim, zero_centered_norm=_zero_centered(cfg))


def _mixer_conv(cfg, layer):
    return nn.GatedShortConv(cfg["hidden_size"], taps=cfg["conv_L_cache"],
                             bias=cfg.get("conv_bias", False))


def _mixer_mamba2(cfg, layer):
    if not cfg.get("use_conv_bias", True) or cfg.get("mamba_proj_bias") \
            or cfg.get("mamba_hidden_act", "silu") != "silu":
        raise NotImplementedError(
            "nn.Mamba2Mixer has a convolution bias, no projection bias "
            "and SiLU")
    # the inner width is heads x head width (the family's modelling
    # code), not ``expand`` x hidden; ``chunk_size`` is the kernel's
    return nn.Mamba2Mixer(
        cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
        cfg["ssm_state_size"], groups=cfg["n_groups"],
        conv_size=cfg["conv_kernel"], epsilon=_eps(cfg),
        time_step=(cfg.get("time_step_min", 1e-3),
                   cfg.get("time_step_max", 1e-1)),
        time_step_floor=cfg.get("time_step_floor", 1e-4))


def _mixer_gdn(cfg, layer):
    return nn.GatedDeltaNet(
        cfg["hidden_size"], cfg["linear_num_key_heads"],
        cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
        cfg["linear_value_head_dim"],
        conv_size=cfg["linear_conv_kernel_dim"], epsilon=_eps(cfg))


def _mixer_kda(cfg, layer):
    lin = cfg["linear_attn_config"]
    return nn.KimiDeltaAttention(
        cfg["hidden_size"], lin["num_heads"], lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"],
        epsilon=cfg["rms_norm_eps"])


def _mixer_mla(cfg, layer):
    if cfg.get("q_lora_rank") is not None:
        raise NotImplementedError("MLA with a low-rank query projection")
    # a file that does not say ``mla_use_nope`` rotates its ``d_pe`` part
    rope = None
    if not cfg.get("mla_use_nope", False):
        if cfg.get("rope_scaling") is not None:
            raise NotImplementedError(
                "MLA with rope_scaling (scaled frequencies and the "
                "softmax's mscale)")
        rope = {"rope_theta": cfg["rope_theta"],
                "interleave": cfg.get("rope_interleave", False)}
    return nn.MLAttention(
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], cfg["kv_lora_rank"],
        epsilon=cfg["rms_norm_eps"], rope=rope)


def _ffn_dense(cfg):
    if "mlp_hidden_act" in cfg:
        return nn.PlainFFN(cfg["hidden_size"], cfg["intermediate_size"],
                           activation=cfg["mlp_hidden_act"])
    return nn.GatedFFN(cfg["hidden_size"], cfg["intermediate_size"],
                       activation=cfg.get("hidden_act", "silu"))


def _ffn_moe(cfg):
    # a file that does not say how its router scores (under either key)
    # and says norm_topk_prob follows the Qwen-MoE convention: softmax
    # over all experts, then the top k, renormalised if it says so
    score = _first(cfg, "moe_router_activation_func", "scoring_func",
                   default="softmax" if "norm_topk_prob" in cfg
                   else "sigmoid")
    if score not in SCORE_FUNCS:
        raise NotImplementedError(
            f"router scores {score!r}: {sorted(SCORE_FUNCS)} are built "
            "(nn.moe.SCORE_FUNCS)")
    if _first(cfg, "num_expert_group", "n_group", default=1) != 1 \
            or cfg.get("topk_group", 1) != 1:
        raise NotImplementedError("group-limited routing")
    experts = _first(cfg, "num_experts", "n_routed_experts")
    width = cfg["moe_intermediate_size"]
    shared = _first(cfg, "num_shared_experts", "n_shared_experts",
                    default=0) * cfg.get(
        "moe_shared_expert_intermediate_size", width)
    # a file with this key has ONE shared expert of that width, and a
    # sigmoid gate on its output
    shared_gate = "shared_expert_intermediate_size" in cfg
    if shared_gate:
        if shared:
            raise NotImplementedError(
                "shared_expert_intermediate_size beside a count of shared "
                "experts")
        shared = cfg["shared_expert_intermediate_size"]
    # a file with ``mlp_hidden_act`` has plain experts, relu(x U)^2 D
    plain = "mlp_hidden_act" in cfg
    if plain and cfg["mlp_hidden_act"] != "relu2":
        raise NotImplementedError(
            f"plain experts of {cfg['mlp_hidden_act']!r}: relu2 is built")
    return nn.SparseMoELayer(
        cfg["hidden_size"], width, experts,
        _first(cfg, "num_experts_per_token", "num_experts_per_tok"),
        experts_held=cfg.get("experts_held", experts),
        expert_offset=cfg.get("expert_offset", 0),
        scaling=cfg.get("routed_scaling_factor", 1.0),
        renormalize=cfg.get("moe_renormalize",
                            cfg.get("norm_topk_prob", True)),
        shared_width=shared or None, score_func=score, gated=not plain,
        shared_gate=shared_gate)


MIXERS = {"conv": _mixer_conv, "gdn": _mixer_gdn, "gqa": _mixer_gqa,
          "kda": _mixer_kda, "mamba2": _mixer_mamba2, "mla": _mixer_mla}
FFNS = {"dense": _ffn_dense, "moe": _ffn_moe}


#: keys by which a config states an attention variant that no mixer
#: here builds: refused by name, never read past
_UNBUILT_ATTENTION = {
    "d_rel": "a relative-position term on the scores",
    "use_sconv": "a short convolution inside the attention layer",
    "log_scaling_alpha": "a length-dependent scale on the scores"}


def mixer_kind(cfg, layer: int):
    """``layer`` counts from 1, as the config's layer lists do. None for
    a pattern's layer that is a feed-forward alone."""
    for key, what in _UNBUILT_ATTENTION.items():
        if cfg.get(key):
            raise NotImplementedError(f"{key}: {what} is not built")
    if "hybrid_override_pattern" in cfg:
        return _pattern_kinds(cfg, layer)[0]
    if "layer_types" in cfg:
        kind = cfg["layer_types"][layer - 1]
        if kind not in _LAYER_TYPES:
            raise NotImplementedError(
                f"layer_types entry {kind!r}: {sorted(_LAYER_TYPES)} are "
                "built")
        return _LAYER_TYPES[kind]
    if "full_attention_interval" in cfg:
        if "linear_num_value_heads" not in cfg:
            raise NotImplementedError(
                "full_attention_interval without linear_num_value_heads: "
                "which mixer the other layers have is not said")
        return "gdn" if layer % cfg["full_attention_interval"] else "gqa"
    lin = cfg.get("linear_attn_config") or {}
    return "kda" if layer in lin.get("kda_layers", ()) else "mla"


def ffn_kind(cfg, layer: int):
    """None for a pattern's layer that is a token mixer alone."""
    if "hybrid_override_pattern" in cfg:
        return _pattern_kinds(cfg, layer)[1]
    if "mlp_layer_types" in cfg:
        return {"sparse": "moe", "dense": "dense"}[
            cfg["mlp_layer_types"][layer - 1]]
    dense = layer <= _first(cfg, "first_k_dense_replace",
                            "num_dense_layers", default=0) \
        or _first(cfg, "num_experts", "n_routed_experts") is None \
        or (layer - 1) % cfg.get("moe_layer_freq", 1) != 0 \
        or layer - 1 in cfg.get("mlp_only_layers", ()) \
        or layer % cfg.get("decoder_sparse_step", 1) != 0
    return "dense" if dense else "moe"


class DecoderBlock(nn.Layer):
    """``input_norm, mixer, post_norm, ffn``, with ``sandwich_norm`` also
    ``mixer_out_norm`` and ``ffn_out_norm`` on the sublayers' outputs; or,
    where the layout gives the layer ONE sublayer, ``norm`` and ``mixer``
    or ``ffn``."""

    def __init__(self, cfg, layer: int):
        super().__init__()
        hidden = cfg["hidden_size"]

        def norm():
            return nn.RMSNorm(hidden, epsilon=_eps(cfg),
                              zero_centered=_zero_centered(cfg))

        self.mixer_kind, self.ffn_kind = (mixer_kind(cfg, layer),
                                          ffn_kind(cfg, layer))
        self.sandwich = bool(cfg.get("sandwich_norm", False))
        if self.mixer_kind and self.ffn_kind:
            self.input_norm = norm()
            self.mixer = MIXERS[self.mixer_kind](cfg, layer)
            if self.sandwich:
                self.mixer_out_norm = norm()
            self.post_norm = norm()
            self.ffn = FFNS[self.ffn_kind](cfg)
            if self.sandwich:
                self.ffn_out_norm = norm()
        else:
            if self.sandwich:
                raise NotImplementedError(
                    "sandwich_norm in a block of one sublayer")
            self.norm = norm()
            if self.mixer_kind:
                self.mixer = MIXERS[self.mixer_kind](cfg, layer)
            else:
                self.ffn = FFNS[self.ffn_kind](cfg)

    def forward(self, x):
        """(x, routing): ``routing`` is the expert layer's [pairs on held
        experts, rows of the rung that ran], zeros for a block without
        one."""
        from .. import ops

        if self.sandwich:
            x = x + self.mixer_out_norm(self.mixer(self.input_norm(x)))
            x = x + self.ffn_out_norm(self.ffn(self.post_norm(x)))
        elif self.mixer_kind and self.ffn_kind:
            x = x + self.mixer(self.input_norm(x))
            x = x + self.ffn(self.post_norm(x))
        else:
            sub = self.mixer if self.mixer_kind else self.ffn
            x = x + sub(self.norm(x))
        routing = self.ffn.last_routing if self.ffn_kind == "moe" \
            else ops.zeros([2], "float32")
        return x, routing


class CausalLM(nn.Layer):
    """See the module docstring. ``cfg`` is kept as ``self.config``."""

    def __init__(self, cfg: dict, recompute: bool = False):
        super().__init__()
        self.config = dict(cfg)
        self.recompute = bool(recompute)
        from ..nn.initializer import Normal
        from ..ops.pallas.counters import bump

        init = nn.ParamAttr(initializer=Normal(
            0.0, cfg.get("initializer_range", 0.02)))
        self.embed = nn.Embedding(cfg["vocab_size"], cfg["hidden_size"],
                                  weight_attr=init)
        self.layers = nn.LayerList([
            DecoderBlock(cfg, n + 1)
            for n in range(cfg["num_hidden_layers"])])
        self.final_norm = nn.RMSNorm(cfg["hidden_size"], epsilon=_eps(cfg),
                                     zero_centered=_zero_centered(cfg))
        # (vocabulary, hidden): the layout the fused cross-entropy
        # streams, and the embedding's: a tied head IS ``embed.weight``
        self.tied = bool(cfg.get("tie_word_embeddings", False))
        if self.tied:
            bump("causal_lm", "tied_head")
        else:
            self.head = self.create_parameter(
                [cfg["vocab_size"], cfg["hidden_size"]], attr=init)
        # the fused cross-entropy takes a bias; this head has none
        self._no_bias = Tensor(jnp.zeros((cfg["vocab_size"],), jnp.float32))
        self.ut_steps = int(cfg.get("total_ut_steps", 1))
        if self.ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.ut_steps}")
        if self.ut_steps > 1:
            # one gate for all passes; its logit decides a token's exit
            self.exit_gate = nn.Linear(cfg["hidden_size"], 1,
                                       weight_attr=init)
            self.exit_entropy_beta = float(cfg["exit_entropy_beta"])
            bump("causal_lm", "ut_steps", times=self.ut_steps)

    @classmethod
    def from_config(cls, cfg: dict, recompute: bool = False) -> "CausalLM":
        return cls(cfg, recompute=recompute)

    @property
    def head_weight(self):
        """The head's (vocabulary, hidden) matrix: the embedding's where
        the two are tied."""
        return self.embed.weight if self.tied else self.head

    def _walk(self, x):
        """One pass: every block once, then the final norm. (normed
        states, per-layer routing (L, 2))."""
        from .. import ops
        from ..optimizer.meta import recompute

        routing = []
        for block in self.layers:
            x, r = recompute(block, x) if self.recompute else block(x)
            routing.append(r)
        return self.final_norm(x), ops.stack(routing, axis=0)

    def hidden_passes(self, input_ids):
        """([h^1 .. h^T], per-layer routing (L, 2) of the last pass):
        the normed output of each of the ``total_ut_steps`` passes over
        the SAME blocks, each pass starting from the one before."""
        import jax

        from ..framework import nan_inf
        from ..ops.pallas.counters import bump

        x = self.embed(input_ids)
        if self.ut_steps == 1:
            x, routing = self._walk(x)
            return [x], routing
        passes = []
        for t in range(1, self.ut_steps + 1):
            with jax.named_scope(f"ut_step{t}"), nan_inf.ut_step(t):
                x, routing = self._walk(x)
            bump("causal_lm", "block_applications", times=len(self.layers))
            passes.append(x)
        return passes, routing

    def hidden(self, input_ids):
        """(final hidden states, per-layer routing (L, 2)): of a looped
        model, the last pass's."""
        passes, routing = self.hidden_passes(input_ids)
        return passes[-1], routing

    def forward(self, input_ids):
        from .. import ops

        h, _ = self.hidden(input_ids)
        return ops.matmul(h, self.head_weight, transpose_y=True)

    def loss(self, input_ids, labels, ignore_index=-100,
             return_routing=False):
        """Mean cross-entropy of each position's logits against
        ``labels`` (already the next token; ``ignore_index`` where there
        is none); of a looped model the expected loss over its exits
        (the module docstring). With ``return_routing``, also the (L, 2)
        routing counters of this call."""
        passes, routing = self.hidden_passes(input_ids)
        if self.ut_steps == 1:
            loss = F.fused_linear_cross_entropy(
                passes[0], self.head_weight, self._no_bias, labels,
                ignore_index=ignore_index)
        else:
            loss = self._expected_exit_loss(passes, labels, ignore_index)
        return (loss, routing) if return_routing else loss

    def _expected_exit_loss(self, passes, labels, ignore_index):
        import jax

        from .. import amp, ops

        with jax.named_scope("ut_exit_loss"):
            # ONE head call on the T passes' rows: (T, B, S, hidden)
            # against the labels T times over, a loss a row back
            stacked = ops.stack(passes, axis=0)
            tiled = ops.stack([labels] * self.ut_steps, axis=0)
            rows = F.fused_linear_cross_entropy(
                stacked, self.head_weight, self._no_bias, tiled,
                ignore_index=ignore_index, reduction="none")
            # the gate reads passes 1 .. T-1 (the last pass takes what is
            # left), in float32 whatever the autocast
            with amp.auto_cast(enable=False):
                logits = ops.squeeze(self.exit_gate(ops.cast(
                    stacked[:-1], "float32")), axis=-1)
            return F.expected_exit_loss(
                rows, logits, labels, beta=self.exit_entropy_beta,
                ignore_index=ignore_index)
