"""Flax model implementations for the named-model zoo.

The reference shipped no model code — it pulled frozen Keras-Applications
graphs (``transformers/keras_applications.py``, Scala ``Models.scala`` +
``ModelFetcher``). A TPU-native framework needs the architectures as
jittable functions, so they are implemented here in Flax (NHWC, bf16
compute / f32 params by default — MXU-friendly).

This package's names and ``models/zoo.py`` are the *image* registry:
``getModelFunction(name)`` promises uint8 NHWC in and features or
class probabilities out. A model over token rows is not a member of
it. ``models/qwen3_next.py``, ``models/axk1.py`` and ``models/ouro.py``
each build their own ``ModelFunction`` (``<module>.model_function(config,
params, seq_len=...)``: int32 tokens in, per-token log-probabilities
out, parameters in bfloat16, plain functions over a parameter tree
instead of Flax modules; what the three share is
``models/lm_blocks.py``) and are imported by their module names;
``TensorTransformer`` takes them like any other ``ModelFunction``.
"""

from sparkdl_tpu.models.inception import InceptionV3  # noqa: F401
from sparkdl_tpu.models.resnet import ResNet50  # noqa: F401
from sparkdl_tpu.models.vgg import VGG16, VGG19  # noqa: F401
from sparkdl_tpu.models.xception import Xception  # noqa: F401
from sparkdl_tpu.models.testnet import TestNet  # noqa: F401

__all__ = ["InceptionV3", "ResNet50", "VGG16", "VGG19", "Xception",
           "TestNet"]
