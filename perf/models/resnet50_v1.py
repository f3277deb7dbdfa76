"""Builder of the ``resnet50_v1`` configuration: the model zoo's network as
a Symbol under ``SoftmaxOutput``, and how its arguments and output map onto
the plain reference beside it (``perf/refs/resnet50_v1.py``)."""
import numpy as np

from perf.refs import resnet50_v1 as ref  # noqa: F401  (the loop takes it from here)

DATA, LABEL = "data", "softmax_label"
_prefix = []


def symbol(cfg, wl):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import resnet
    if not cfg["bottleneck"]:
        raise ValueError("only the bottleneck network has a reference here")
    net = resnet.ResNetV1(resnet.BottleneckV1, list(cfg["layers"]),
                          [cfg["stem_channels"]] + list(cfg["channels"]),
                          classes=cfg["classes"])
    _prefix[:] = [net.prefix]
    return mx.sym.SoftmaxOutput(net(mx.sym.var(DATA)), mx.sym.var(LABEL),
                                name="softmax")


def shapes(cfg, wl):
    size = cfg["image_size"]
    return ({DATA: (wl["batch"], 3, size, size)}, {LABEL: (wl["batch"],)})


def leaf_name(arg_name):
    """The reference's name of one of the program's arguments (the model
    zoo prefixes every name with the network's, ``resnetv10_``)."""
    return arg_name[len(_prefix[0]):]


def row_losses(output, label):
    """Every row's cross-entropy from the fetched softmax output."""
    p = np.asarray(output, np.float64).reshape(len(label), -1)
    picked = p[np.arange(len(label)), np.asarray(label).astype(int)]
    return -np.log(np.maximum(picked, 1e-300))


def step_loss(output, label):
    """Mean cross-entropy from the fetched softmax output: what a fit with a
    ``CrossEntropy`` metric computes on the host."""
    return float(np.mean(row_losses(output, label)))


def items_per_step(cfg, wl):
    return wl["batch"]


step_flops = ref.step_flops
