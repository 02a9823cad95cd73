"""Unit tests for tools/profile_step.py's reduction of a device trace:
op names split into phase and scope, seconds summed by phase, module
scope and Pallas kernel role, and the join with the compiled text."""
import os


def _profile_step():
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "profile_step", os.path.join(repo, "tools", "profile_step.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: a small recorded event list in the shapes a v5e trace gives them:
#: (instruction name, op_name, seconds)
_STEP = "jit(train_step)/"
_EVENTS = [
    ("fusion.12", _STEP + "jvp(loss)/bert/encoder/layer/linear1/"
     "dot_general", 3.0),
    ("fusion.13", _STEP + "transpose(jvp(loss))/bert/encoder/layer/"
     "linear1/dot_general", 5.0),
    ("multiply_reduce_fusion.2", _STEP + "transpose(jvp(loss))/bert/"
     "encoder/layer/norm1/mul", 2.0),
    ("kernel:flash_attention_short_fwd.4", _STEP + "jvp(loss)/bert/encoder/"
     "layer/self_attn/jit(_flash_attention_pallas_short)/pallas/"
     "flash_attention_short_fwd/pallas_call", 1.0),
    ("kernel:fused_xent_bwd.1", _STEP + "transpose(jvp(loss))/pallas/"
     "fused_xent_bwd/pallas_call", 4.0),
    ("kernel:fused_xent_bwd.2", _STEP + "transpose(jvp(loss))/pallas/"
     "fused_xent_bwd/pallas_call", 2.0),
    ("kernel:fused_adamw.9", _STEP + "optimizer/pallas/fused_adamw/"
     "pallas_call", 1.5),
    ("copy.3", None, 1.0),
    ("bitcast_convert_fusion", _STEP + "optimizer/convert_element_type",
     0.5),
]


def test_profile_step_splits_an_op_name_into_phase_and_scope():
    split = _profile_step().split_op_name
    assert split(_EVENTS[0][1]) == (
        "forward", ["loss", "bert", "encoder", "layer", "linear1"])
    assert split(_EVENTS[1][1])[0] == "backward"
    # the jit wrapper, the guard scope and the primitive are not scopes
    assert split(_EVENTS[3][1]) == (
        "forward", ["loss", "bert", "encoder", "layer", "self_attn",
                    "flash_attention_short_fwd"])
    assert split(_EVENTS[6][1]) == ("optimizer",
                                    ["optimizer", "fused_adamw"])
    assert split(_STEP + "optimizer/reshape;" + _STEP + "optimizer/mul") \
        == ("optimizer", ["optimizer"])
    assert split(None) == ("unattributed", [])
    assert split("jit(train_step)/add") == ("other", [])


def test_profile_step_sums_by_phase_scope_and_kernel_role():
    mod = _profile_step()
    out = mod.summarize(_EVENTS, depth=4)
    assert out["total_s"] == 20.0
    assert dict(out["by_phase"]) == {
        "forward": 4.0, "backward": 13.0, "optimizer": 2.0,
        "unattributed": 1.0}
    scopes = dict(out["by_scope"])
    # cut at depth 4: the layer's parts fold into encoder/layer
    assert scopes["loss/bert/encoder/layer"] == 11.0
    assert scopes["loss/fused_xent_bwd"] == 6.0
    assert scopes[mod.NO_SCOPE] == 1.0
    assert sum(scopes.values()) == 20.0
    # numeric suffixes merged: both backward kernels are one role
    assert dict(out["kernels"]) == {
        "fused_xent_bwd": 6.0, "fused_adamw": 1.5,
        "flash_attention_short_fwd": 1.0}
    top = out["owners"][0]
    assert top[0] == "fusion" and top[1] == 8.0
    assert top[2] == [("loss/bert/encoder/layer", 8.0)]
    text = mod.render(out, steps=10)
    assert "| backward | 13.0000 | 65.0% |" in text
    assert "| fused_xent_bwd | 6.0000 | 30.0% |" in text


def test_profile_step_counts_an_enclosing_operation_once():
    # a cond that ran a gather and a kernel, then a fusion after it
    got = _profile_step().self_seconds([
        ("fusion.2", 8e9, 3e9), ("kernel:fused_xent_rows512_fwd", 1.5e9, 6e9),
        ("cond", 0.0, 8e9), ("gather_fusion", 0.5e9, 1e9)])
    assert got == [("cond", 1.0), ("gather_fusion", 1.0),
                   ("kernel:fused_xent_rows512_fwd", 6.0), ("fusion.2", 3.0)]
    assert sum(s for _, s in got) == 11.0


def test_profile_step_joins_instructions_with_the_compiled_text():
    mod = _profile_step()
    hlo = """HloModule jit_train_step, is_scheduled=true
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %add.5 = f32[8]{0} add(%p, %p), metadata={op_name="jit(train_step)/jvp(loss)/nsp/add" source_file="x.py"}
}
ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %fusion.12 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(loss)/bert/pooler/dot_general" source_file="x.py" source_line=3}
  %fused_xent_fwd.1 = f32[8]{0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(loss)/pallas/fused_xent_fwd/pallas_call"}
  ROOT %copy.3 = f32[8]{0} copy(%fused_xent_fwd.1)
}
"""
    scopes = mod.scopes_from_text(hlo)
    assert scopes["fusion.12"].endswith("bert/pooler/dot_general")
    assert scopes["fused_xent_fwd.1"].endswith("fused_xent_fwd/pallas_call")
    assert "copy.3" not in scopes
    events = [(n, scopes.get(n.removeprefix("kernel:")), s) for n, s in
              (("fusion.12", 2.0), ("kernel:fused_xent_fwd.1", 1.0),
               ("copy.3", 1.0))]
    out = mod.summarize(events)
    assert dict(out["by_scope"]) == {
        "loss/bert/pooler": 2.0, "loss/fused_xent_fwd": 1.0,
        mod.NO_SCOPE: 1.0}


def test_profile_step_lists_what_a_scope_is_made_of():
    """``--within``: the operations under a named scope by family and
    result shape, from the compiled text's shapes (a tuple's first)."""
    mod = _profile_step()
    hlo = """ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %fusion.12 = f32[2,8192,4096]{2,1,0:T(8,128)} fusion(%a), kind=kLoop, calls=%c.1
  %fusion.13 = f32[2,8192,4096]{2,1,0:T(8,128)} fusion(%a), kind=kLoop, calls=%c.2
  %mamba2_conv.3 = (bf16[2,8192,1024]{2,1,0:T(8,128)(2,1)}, f32[2,5,8,1024]{3,2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
  ROOT %pad.7 = bf16[2,8192,10304]{2,1,0} pad(%a, %a), padding=0_0
}
"""
    shapes = mod.shapes_from_text(hlo)
    assert shapes == {"fusion.12": "f32[2,8192,4096]",
                      "fusion.13": "f32[2,8192,4096]",
                      "mamba2_conv.3": "bf16[2,8192,1024]",
                      "pad.7": "bf16[2,8192,10304]"}
    mixer = _STEP + "transpose(jvp(loss))/layer/mixer/"
    events = [("fusion.12", mixer + "checkpoint/short_conv/mul", 2.0),
              ("fusion.13", mixer + "checkpoint/short_conv/add", 1.0),
              ("pad.7", mixer + "checkpoint/short_conv/pad", 0.5),
              ("kernel:mamba2_conv.3",
               _STEP + "jvp(loss)/layer/mixer/short_conv/pallas/"
               "mamba2_conv/pallas_call", 0.25),
              ("fusion.99", mixer + "gated_norm/mul", 4.0),
              ("copy.1", None, 8.0)]
    got = mod.within(events, ("short_conv", "ssd_scan"), shapes)
    assert got["short_conv"] == {
        "total_s": 3.75,
        "by_phase": [("backward", 3.5), ("forward", 0.25)],
        "by_op": [("fusion f32[2,8192,4096]", 3.0),
                  ("pad bf16[2,8192,10304]", 0.5),
                  ("kernel:mamba2_conv bf16[2,8192,1024]", 0.25)]}
    assert got["ssd_scan"] == {"total_s": 0.0, "by_phase": [], "by_op": []}
    text = mod.render_within(got)
    assert "within short_conv: 3.7500 s (backward 3.5000, forward 0.2500)" \
        in text
    assert "| fusion f32[2,8192,4096] | 3.0000 |" in text
