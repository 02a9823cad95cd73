"""2.0-alpha API surface parity (reference python/paddle/{nn,tensor,
optimizer} at v1.8): pre-rename spellings resolve, the namespaces close
to zero missing names, and the genuinely-new layers compute correctly."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.nn.compat20 as c20


def _np(x):
    return np.asarray(x.value if hasattr(x, "value") else x)


def test_reference_nn_all_resolves():
    missing = [n for n in c20._REFERENCE_NN_ALL if not hasattr(nn, n)]
    assert not missing, missing


def test_optimizer_aliases():
    import paddle_tpu.optimizer as opt
    assert opt.SGDOptimizer is opt.SGD
    assert opt.MomentumOptimizer is opt.Momentum
    assert opt.ExponentialMovingAverage is opt.EMA
    assert opt.StepLR is opt.lr.StepDecay
    assert opt._LRScheduler is opt.lr.LRScheduler
    assert callable(opt.PipelineOptimizer)


def test_tensor_namespace():
    import paddle_tpu.tensor as T
    r = T.reduce_mean(np.asarray([[1.0, 3.0]]), dim=1)
    np.testing.assert_allclose(_np(r), [2.0])
    assert int(_np(T.numel(np.ones((2, 5))))) == 10
    out = T.elementwise_sum([np.ones(3), np.ones(3), np.ones(3)])
    np.testing.assert_allclose(_np(out), 3.0)
    fd = T.elementwise_floordiv(np.asarray([7]), np.asarray([2]))
    assert int(_np(fd)[0]) == 3


def test_lowercase_class_aliases_construct():
    conv = nn.Conv2d(3, 8, 3)          # pre-rename spelling
    x = paddle.to_tensor(np.random.randn(1, 3, 8, 8).astype(np.float32))
    y = conv(x)
    assert tuple(y.shape) == (1, 8, 6, 6)
    pool = nn.MaxPool2d(2)
    assert tuple(pool(y).shape) == (1, 8, 3, 3)
    pad = nn.ZeroPad2d([1, 1, 1, 1])
    assert tuple(pad(y).shape) == (1, 8, 8, 8)
    rp = nn.ReplicationPad2d([1, 1, 1, 1])
    assert tuple(rp(y).shape) == (1, 8, 8, 8)


def test_bilinear_tensor_product():
    layer = nn.BilinearTensorProduct(3, 4, 5)
    x1 = paddle.to_tensor(np.random.randn(2, 3).astype(np.float32))
    x2 = paddle.to_tensor(np.random.randn(2, 4).astype(np.float32))
    y = layer(x1, x2)
    assert tuple(y.shape) == (2, 5)
    # closed form check against einsum
    w = _np(layer.weight)
    b = _np(layer.bias)
    exp = np.einsum("bi,kij,bj->bk", _np(x1), w, _np(x2)) + b
    # unseeded float32 sums that can cancel: a relative bound alone
    # failed once in a whole run (PR 29) on a difference of 9e-8
    np.testing.assert_allclose(_np(y), exp, rtol=1e-5, atol=1e-6)


def test_pairwise_distance():
    pd = nn.PairwiseDistance(p=2.0)
    x = np.asarray([[0.0, 0.0], [1.0, 1.0]], np.float32)
    y = np.asarray([[3.0, 4.0], [1.0, 1.0]], np.float32)
    d = _np(pd(paddle.to_tensor(x), paddle.to_tensor(y)))
    np.testing.assert_allclose(d, [5.0, np.sqrt(2) * 1e-6], atol=1e-4)


def test_row_conv_lookahead():
    rc = nn.RowConv(4, future_context_size=2)
    x = paddle.to_tensor(np.random.randn(2, 6, 4).astype(np.float32))
    y = rc(x)
    assert tuple(y.shape) == (2, 6, 4)
    # the last timestep only sees itself (zero future padding)
    w = _np(rc.weight)
    exp_last = _np(x)[:, -1] * w[0]
    np.testing.assert_allclose(_np(y)[:, -1], exp_last, rtol=1e-5)


def test_hsigmoid_loss_decreases_under_training():
    num_classes, dim, b = 8, 16, 32
    rng = np.random.RandomState(0)
    head = nn.HSigmoid(dim, num_classes)
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=list(head.parameters()))
    x = paddle.to_tensor(rng.randn(b, dim).astype(np.float32))
    label = paddle.to_tensor(rng.randint(0, num_classes, b))
    first = None
    for _ in range(25):
        loss = head(x, label).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        first = first if first is not None else float(loss.value)
    assert float(loss.value) < first * 0.5, (first, float(loss.value))


def test_pool2d_facade():
    p = nn.Pool2D(pool_size=2, pool_type="avg", pool_stride=2)
    x = paddle.to_tensor(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    y = _np(p(x))
    np.testing.assert_allclose(y[0, 0], [[2.5, 4.5], [10.5, 12.5]])
    g = nn.Pool2D(pool_type="max", global_pooling=True)
    assert float(_np(g(x)).reshape(())) == 15.0


def test_instance_norm_rank_dispatch():
    innorm = nn.InstanceNorm(4)
    for shape in [(2, 4, 8), (2, 4, 8, 8)]:
        x = paddle.to_tensor(np.random.randn(*shape).astype(np.float32))
        y = _np(innorm(x))
        assert y.shape == shape
        # per-instance-channel normalization: mean ~ 0
        assert abs(y.reshape(2, 4, -1).mean(-1)).max() < 1e-4


def test_weight_norm_reparametrization():
    lin = nn.Linear(4, 3)
    w0 = _np(lin.weight).copy()
    nn.weight_norm(lin, "weight", dim=0)
    assert hasattr(lin, "weight_g") and hasattr(lin, "weight_v")
    x = paddle.to_tensor(np.random.randn(2, 4).astype(np.float32))
    y1 = _np(lin(x))
    # effective weight reproduces the original at init
    np.testing.assert_allclose(_np(lin.weight), w0, rtol=1e-5, atol=1e-6)
    nn.remove_weight_norm(lin, "weight")
    assert not hasattr(lin, "_weight_norm_hook")
    y2 = _np(lin(x))
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-6)


def test_remove_weight_norm_weight_is_trainable_again():
    lin = nn.Linear(4, 3)
    nn.weight_norm(lin, "weight")
    nn.remove_weight_norm(lin, "weight")
    # the restored weight must be the parameter forward actually reads
    x = paddle.to_tensor(np.ones((1, 4), np.float32))
    y1 = _np(lin(x))
    lin.weight.set_value(np.zeros_like(_np(lin.weight)))
    y2 = _np(lin(x))
    assert not np.allclose(y1, y2) or np.allclose(y1, _np(lin.bias))


def test_instance_norm_registers_parameters():
    innorm = nn.InstanceNorm(4)
    assert len(list(innorm.parameters())) >= 2
    assert innorm.state_dict()


def test_mul_restores_reference_shape():
    import paddle_tpu.tensor as T
    out = T.mul(np.ones((2, 3, 4), np.float32),
                np.ones((4, 5), np.float32), x_num_col_dims=2)
    assert _np(out).shape == (2, 3, 5)


def test_logsigmoid():
    x = np.asarray([-2.0, 0.0, 3.0], np.float32)
    out = _np(nn.logsigmoid(x))
    np.testing.assert_allclose(out, np.log(1 / (1 + np.exp(-x))),
                               rtol=1e-5)
