"""BERT-base pretraining via the public API (the model of
benchmarks/configs/bert-base.json as a user-style script; set
BERT_SMOKE=1 for a tiny CPU run)."""
import os
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import amp, optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.bert import BertConfig, BertForPretraining

smoke = os.environ.get("BERT_SMOKE") == "1"
paddle.seed(0)
print("device:", paddle.get_device())

cfg = BertConfig.tiny() if smoke else BertConfig.base()
batch, seq, steps = (4, 32, 5) if smoke else (128, 128, 50)
model = BertForPretraining(cfg)
opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())


def loss_fn(m, ids, tt, mlm, nsp):
    with amp.auto_cast(level="O1", dtype="bfloat16"):
        return m.loss(ids, tt, mlm, nsp)


step = TrainStep(model, loss_fn, opt)

rng = np.random.RandomState(0)
ids = paddle.to_tensor(
    rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
tt = paddle.to_tensor(np.zeros((batch, seq), np.int32))
mlm = paddle.to_tensor(
    rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
nsp = paddle.to_tensor(rng.randint(0, 2, (batch,)).astype(np.int32))

t0 = time.time()
loss0 = float(step(ids, tt, mlm, nsp))
print(f"compile+first step: {time.time() - t0:.1f}s, loss {loss0:.4f}")
t0 = time.time()
for i in range(steps):
    loss = step(ids, tt, mlm, nsp)
loss = float(loss)
dt = time.time() - t0
print(f"{steps} steps, loss {loss0:.4f} -> {loss:.4f}, "
      f"{batch * seq * steps / dt:,.0f} tokens/s")
if smoke:
    assert np.isfinite(loss), loss   # 5 tiny steps: finite is the gate
else:
    assert loss < loss0, "loss must decrease"
print("OK")
