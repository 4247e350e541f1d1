"""apex_tpu.models — reference models for the example workloads.

Mirrors the reference's app layer (``examples/imagenet``, ``examples/simple``,
``apex/transformer/testing/standalone_{gpt,bert}.py``): a ResNet family for
the imagenet O2 slice, and standalone GPT/BERT for the transformer runtime.
"""

from apex_tpu.models.resnet import ResNet, ResNet18, ResNet50, ResNet101  # noqa: F401
