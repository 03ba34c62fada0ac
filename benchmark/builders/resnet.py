"""Builds the program's ResNet-50 from a configuration file as
``models.perf`` builds it (``resnet(1000, depth=50, IMAGENET)`` behind the
NCHW facade, channels-last inside, log-softmax on top), and hands its
weight arrays to the plain reference in execution order."""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.builders.common import install_on_device, n_parameters


def build(config: Dict[str, Any], key, on_tpu: bool = True):
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.resnet import DatasetType, resnet
    if int(config["depth"]) != 50:
        raise ValueError("this builder makes the 50-layer network")
    model = resnet(int(config["num_classes"]), depth=50,
                   dataset=DatasetType.IMAGENET,
                   layout=config.get("program", {}).get("layout", "NHWC"))
    model.add(nn.LogSoftMax())

    def he(m, p, key):
        """``models.resnet.model_init`` as a pure function of the key:
        He-normal convolutions, unit batch-norm scale, zero biases."""
        if isinstance(p, list):
            return [he(c, pc, jax.random.fold_in(key, i))
                    for i, (c, pc) in enumerate(zip(m.children, p))]
        if isinstance(m, nn.SpatialConvolution):
            n = m.kernel_w * m.kernel_w * m.n_output_plane
            out = {"weight": jax.random.normal(key, p["weight"].shape)
                   * math.sqrt(2.0 / n)}
            if "bias" in p:
                out["bias"] = jnp.zeros_like(p["bias"])
            return out
        if isinstance(m, nn.SpatialBatchNormalization):
            return {k: (jnp.ones_like(v) if k == "weight"
                        else jnp.zeros_like(v)) for k, v in p.items()}
        if isinstance(m, nn.Linear) and "bias" in p:
            return dict(p, bias=jnp.zeros_like(p["bias"]))
        return p

    def init(key):
        k1, k2 = jax.random.split(key)
        return he(model, model._init_params(k1), k2)

    return install_on_device(model, key, init)


def reference_weights(model) -> Dict[str, Any]:
    """Convolutions, batch norms and the classifier in the order a forward
    pass meets them (each block: its three convolutions, then the
    shortcut's), as ``references/resnet.py`` consumes them."""
    import bigdl_tpu.nn as nn
    convs, bns, fc = [], [], None
    for m in model.modules():
        if isinstance(m, nn.SpatialConvolution):
            convs.append({"w": m.params["weight"],
                          "b": m.params.get("bias")})
        elif isinstance(m, nn.SpatialBatchNormalization):
            bns.append({"w": m.params["weight"], "b": m.params["bias"]})
        elif isinstance(m, nn.Linear):
            fc = {"w": m.params["weight"], "b": m.params["bias"]}
    return {"convs": convs, "bns": bns, "fc": fc}


def describe(config: Dict[str, Any], model) -> Dict[str, Any]:
    return {"parameters": n_parameters(model)}
