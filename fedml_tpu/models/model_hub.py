"""``fedml_tpu.models.create`` — the model factory.

Parity: ``model/model_hub.py:19-83`` (name×dataset dispatch). Returns a flax
module; parameters are created by the engine with an explicit PRNG key so
every client/server sees identical init given ``args.random_seed``.
"""
from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def create(args: Any, output_dim: int = 10) -> nn.Module:
    name = str(getattr(args, "model", "lr")).lower()
    from fedml_tpu.models.cv.cnn import CNNCifar, CNNFemnist
    from fedml_tpu.models.cv.resnet import resnet18, resnet20, resnet56
    from fedml_tpu.models.linear.lr import MLP, LogisticRegression
    from fedml_tpu.models.nlp.rnn import RNNOriginalFedAvg, RNNStackOverflow

    dataset = str(getattr(args, "dataset", "")).lower()
    groups = None if getattr(args, "group_norm_channels", 2) in (0, None) else int(
        getattr(args, "group_norm_channels", 2)
    )

    if name in ("lr", "logistic_regression"):
        return LogisticRegression(output_dim=output_dim)
    if name == "mlp":
        return MLP(hidden_dim=int(getattr(args, "hidden_dim", 128)), output_dim=output_dim)
    if name in ("cnn", "cnn_dropout"):
        if "cifar" in dataset or "cinic" in dataset:
            return CNNCifar(output_dim=output_dim)
        return CNNFemnist(output_dim=output_dim)
    if name in ("lenet", "lenet5", "mnn_lenet"):
        # cross-device on-device model (reference: model/mobile/mnn_lenet)
        from fedml_tpu.models.cv.cnn import LeNet5

        return LeNet5(output_dim=output_dim)
    if name in ("segnet", "deeplab", "unet"):
        from fedml_tpu.simulation.sp.fedseg import SegNet

        return SegNet(n_classes=output_dim,
                      width=int(getattr(args, "seg_width", 16)))
    if name in ("resnet18", "resnet18_gn"):
        return resnet18(output_dim=output_dim, groups=groups)
    if name in ("resnet20",):
        return resnet20(output_dim=output_dim, groups=groups)
    if name in ("resnet56", "resnet56_gn"):
        return resnet56(output_dim=output_dim, groups=groups)
    if name in ("mobilenet", "mobilenet_v3", "mobilenetv3"):
        from fedml_tpu.models.cv.mobilenet import MobileNetV3Small

        return MobileNetV3Small(output_dim=output_dim)
    if name in ("efficientnet", "efficientnet_b0", "efficientnet_lite0"):
        from fedml_tpu.models.cv.efficientnet import EfficientNetLite0

        return EfficientNetLite0(output_dim=output_dim)
    if name in ("vgg11", "vgg16", "vgg"):
        from fedml_tpu.models.cv.vgg import vgg11, vgg16

        return vgg16(output_dim) if name == "vgg16" else vgg11(output_dim)
    if name in ("darts", "fednas"):
        from fedml_tpu.models.cv.darts import DARTSNetwork

        return DARTSNetwork(
            output_dim=output_dim,
            channels=int(getattr(args, "darts_channels", 16)),
            n_cells=int(getattr(args, "darts_cells", 2)),
        )
    if name in ("rnn", "lstm"):
        if "stackoverflow" in dataset or "reddit" in dataset:
            return RNNStackOverflow(vocab_size=max(output_dim, 4))
        return RNNOriginalFedAvg(vocab_size=max(output_dim, 4))
    if name in ("llama", "llama_lora", "transformer", "zaya", "nemotron_h",
                "glm4_moe_lite"):
        from fedml_tpu.models.llm import config_from_args

        return config_from_args(args, vocab_size=max(output_dim, 32)).module()
    raise ValueError(f"unknown model {name!r}")


def init_params(model: nn.Module, args: Any, sample_input: Any) -> Any:
    key = jax.random.key(int(getattr(args, "random_seed", 0)))
    x = jnp.asarray(sample_input)
    return model.init(key, x)


def example_input(args: Any, feature_shape: Tuple[int, ...], int_tokens: bool = False):
    batch = int(getattr(args, "batch_size", 32))
    if int_tokens:
        return np.zeros((batch, *feature_shape), dtype=np.int32)
    return np.zeros((batch, *feature_shape), dtype=np.float32)
