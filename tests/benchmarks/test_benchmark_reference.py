"""The plain references agree with the program at tiny sizes on the CPU,
and the comparisons behind ``correct`` fail their control: the same
numbers computed one precision step lower."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, traffic
from benchmarks.reference import bert as bert_ref
from benchmarks.reference import decoder as decoder_ref


@pytest.fixture(scope="module")
def bert(bench_root):
    root, _ = bench_root
    cell, cfg = harness.load_cell("bert-tiny.pretrain", root)
    driver = harness.load_driver(cfg, root)
    batches = traffic.train_batches(cell["traffic"], cfg["vocab_size"], 5)
    return cell, cfg, driver, batches


def _rel_l2(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def test_bert_reference_agrees_with_the_programs_loss_and_gradients(bert):
    import paddle_tpu as paddle

    cell, cfg, driver, batches = bert
    params = driver.make_params(cfg, 5)
    loop = driver.Loop(cfg, cell, dict(params), 5)     # float32, eval mode
    ids, seg, labels, nsp = batches[0]
    got = float(loop.model.loss(*(paddle.to_tensor(a) for a in
                                  (ids, seg, labels, nsp))))
    rows = len(ids)
    n_lab = int((labels != -100).sum())
    refcfg = driver.reference_config(cfg)

    def whole(p):
        return sum(bert_ref.block_loss(p, refcfg, b, rows, n_lab)
                   for b in bert_ref._blocks(batches[0], 4))

    want, grads = jax.value_and_grad(whole)(params)
    assert got == pytest.approx(float(want), rel=2e-6)

    def program_loss(p):
        saved = {n: q._value for n, q in loop.model.named_parameters()}
        loop.model.load_param_pytree(p)
        try:
            out = loop.model.loss(*(paddle.to_tensor(a) for a in
                                    (ids, seg, labels, nsp)))
            return out.value
        finally:
            for n, q in loop.model.named_parameters():
                q._value = saved[n]

    pgrads = jax.grad(program_loss)(params)
    floor = float(np.median([float(jnp.linalg.norm(g))
                             for g in grads.values()]))
    for name, g in grads.items():
        err = float(jnp.linalg.norm(pgrads[name] - g))
        assert err <= 1e-4 * max(float(jnp.linalg.norm(g)), floor), name


def test_training_comparison_passes_float32_and_fails_lower_precision(bert):
    cell, cfg, driver, batches = bert
    limits = cell["correct"]["limits"]
    params = driver.make_params(cfg, 6)
    refcfg = driver.reference_config(cfg)
    ref = bert_ref.train(params, refcfg, batches[:3], cell["optimizer"],
                         block_rows=4)
    # the program itself, float32: inside every limit
    loop = driver.Loop(cfg, cell, dict(driver.make_params(cfg, 6)), 6)
    prog = driver.first_steps(loop, cfg, batches, 6, 3)
    sound = driver.compare(prog, ref, limits)
    assert all(c["ok"] for c in sound), sound
    # the control: the reference with fp8 operands in the program's place
    low = bert_ref.train(params, refcfg, batches[:3], cell["optimizer"],
                         block_rows=4, matmuls=bert_ref.fp8_matmuls)
    control = driver.compare(low, ref, limits)
    assert not all(c["ok"] for c in control), control
    # and the program under bfloat16 autocast, where float32 is stated
    cfg16 = dict(cfg, program=dict(cfg["program"], amp_level="O1"))
    loop16 = driver.Loop(cfg16, cell, dict(driver.make_params(cfg, 6)), 6)
    prog16 = driver.first_steps(loop16, cfg16, batches, 6, 3)
    assert not all(c["ok"] for c in driver.compare(prog16, ref, limits))
    # a part of the batch left out moves the loss past its limit
    short = [tuple(a[:6] for a in b) for b in batches[:3]]
    part = bert_ref.train(params, refcfg, short, cell["optimizer"],
                          block_rows=2)
    checks = {c["name"]: c for c in driver.compare(part, ref, limits)}
    assert not checks["loss_gap"]["ok"]


@pytest.fixture(scope="module")
def decoder(bench_root):
    root, _ = bench_root
    cell, cfg = harness.load_cell("opt-tiny.chat", root)
    driver = harness.load_driver(cfg, root)
    return cell, cfg, driver, driver.make_params(cfg, 9)


def test_decoder_reference_agrees_with_the_programs_dense_forward(decoder):
    from paddle_tpu.inference.decode.model import (DecodeModelConfig,
                                                   dense_forward,
                                                   init_decode_params)

    _cell, cfg, _driver, params = decoder
    heads = cfg["num_attention_heads"]
    mcfg = DecodeModelConfig(cfg["vocab_size"], cfg["num_hidden_layers"],
                             heads, cfg["hidden_size"] // heads,
                             cfg["ffn_dim"], cfg["max_position_embeddings"])
    # the benchmark's seeded weights carry the program's keys and shapes
    theirs = init_decode_params(mcfg, 0)
    assert {k: v.shape for k, v in theirs.items()} == \
        {k: v.shape for k, v in params.items()}
    assert float(jnp.std(params["l0.w2"])) == pytest.approx(
        cfg["ffn_dim"] ** -0.5, rel=0.05)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 96)
    want = dense_forward(mcfg, params, jnp.asarray(tokens[None],
                                                   jnp.int32))[0]
    got = decoder_ref.logits_rows(cfg, params, tokens, 10, 64)
    assert float(jnp.max(jnp.abs(got - want[10:74]))) < 1e-4


def test_serving_comparison_fails_tokens_chosen_in_bfloat16(decoder):
    cell, cfg, driver, params = decoder
    limit = cell["correct"]["limits"]["served_token_gap"]
    rng = np.random.default_rng(1)
    half = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
            for k, v in params.items()}

    def record(idx, served_by):
        prompt = rng.integers(0, cfg["vocab_size"], 40).tolist()
        served = []
        for _ in range(24):                 # greedy, teacher-forced
            toks = np.asarray(prompt + served)
            row = decoder_ref.logits_rows(cfg, served_by, np.pad(
                toks, (0, 128 - len(toks))), len(toks) - 1, 1)
            served.append(int(jnp.argmax(row[0])))
        return {"req": traffic.Request(idx, 0.0, prompt, 24),
                "tokens": served, "times": [0.0] * 24, "error": None}

    sound = driver.served_token_gaps(
        cfg, params, [record(i, params) for i in range(3)])
    assert sound["tokens"] == 72 and sound["widest_gap"] <= limit
    control = driver.served_token_gaps(
        cfg, params, [record(i, half) for i in range(3)])
    assert control["widest_gap"] > limit
    assert control["not_best"] >= 1
