"""Model zoo coverage (reference: python/mxnet/gluon/model_zoo/vision/)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo.vision import get_model


@pytest.mark.parametrize("name,hw", [
    pytest.param("densenet121", 64, marks=pytest.mark.slow),
    pytest.param("squeezenet1.1", 224, marks=pytest.mark.slow),
    ("vgg11_bn", 32),
    ("resnet18_v1", 32),
])
def test_zoo_forward(name, hw):
    mx.random.seed(0)
    net = get_model(name, classes=10)
    net.initialize()
    x = nd.array(onp.random.randn(2, 3, hw, hw).astype("float32"))
    y = net(x)
    assert y.shape == (2, 10)
    assert onp.isfinite(y.asnumpy()).all()


def test_zoo_registry_complete():
    # every family the reference zoo ships must resolve
    for name in ["resnet50_v1", "resnet101_v2", "alexnet", "mobilenet1.0",
                 "mobilenetv2_1.0", "vgg16", "vgg16_bn", "densenet169",
                 "squeezenet1.0", "inceptionv3"]:
        net = get_model(name, classes=7)
        assert net is not None


def test_inception_v3_structure():
    # forward at 299 is exercised in bench-style runs; here check the tower
    # structure builds and parameters initialize
    net = get_model("inceptionv3", classes=10)
    net.initialize()
    n_params = len(net.collect_params())
    assert n_params > 100    # 94 convs + BNs


def test_resnet_v1_trains_and_eval_reads_running_stats():
    """The one tier-1 run of ``ResNetV1``'s own forward under a trainer: a
    few ``gluon.Trainer`` steps on a two-stage bottleneck net at 32 x 32
    bring the loss down, move the BatchNorm running statistics, and an
    eval-mode forward reads those statistics without changing them."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo.vision.resnet import (BottleneckV1,
                                                         ResNetV1)

    mx.random.seed(0)
    net = ResNetV1(BottleneckV1, [1, 1], [16, 32, 64], classes=4)
    net.initialize()
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(8, 3, 32, 32).astype("float32"))
    y = nd.array(rng.randint(0, 4, (8,)).astype("float32"))
    net(x)  # complete deferred init

    def running():
        return {k: v.data().asnumpy().copy()
                for k, v in net._collect_params_with_prefix().items()
                if "running" in k}

    fresh = running()
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    losses = []
    for _ in range(6):
        with autograd.record():
            loss = lossfn(net(x), y).mean()
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    assert onp.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses

    trained = running()
    assert any(not onp.array_equal(trained[k], fresh[k]) for k in fresh)
    out = net(x).asnumpy()
    assert out.shape == (8, 4) and onp.isfinite(out).all()
    onp.testing.assert_array_equal(net(x).asnumpy(), out)
    for k, v in running().items():
        onp.testing.assert_array_equal(v, trained[k])
    name, stat = next((k, v) for k, v in
                      net._collect_params_with_prefix().items()
                      if k.endswith("running_mean"))
    stat.set_data(nd.array(trained[name] + 1.0))
    assert not onp.allclose(net(x).asnumpy(), out)
