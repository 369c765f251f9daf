"""ResNet-18 with GroupNorm — the fed_cifar100 benchmark model.

Reference: fedml_api/model/cv/resnet_gn.py:183 ``resnet18`` with
``GroupNorm2d`` (group_normalization.py) in place of BatchNorm — the
normalization choice "Adaptive Federated Optimization" (arXiv:2003.00295)
uses for cross-device FL, since BN running statistics are ill-defined across
non-IID clients. GroupNorm has no running state, so the model's variables are
pure ``params`` (no mutable collections) — ideal for vmapped client training.

ImageNet-style basic-block layout [2,2,2,2] at 64/128/256/512 planes; for the
24x24 fed_cifar100 crops the 7x7-stride-2 stem + maxpool is replaced by a 3x3
stem (``small_images=True``, the standard CIFAR adaptation).
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.models.common import LiveTapConv


class GNBasicBlock(nn.Module):
    planes: int
    stride: int = 1
    channels_per_group: int = 32

    def _norm(self, channels):
        return nn.GroupNorm(
            num_groups=max(1, channels // self.channels_per_group))

    @nn.compact
    def __call__(self, x, train: bool = False):
        identity = x
        # on a map smaller than the kernel's reach (the last stage at 24x24
        # crops is 1x1) the 3x3 convolutions read only their live taps; the
        # names are nn.Conv's own, so the parameter tree is unchanged
        out = LiveTapConv(self.planes, (3, 3), padding=1, use_bias=False,
                          strides=(self.stride, self.stride), name="Conv_0")(x)
        out = nn.relu(self._norm(self.planes)(out))
        out = LiveTapConv(self.planes, (3, 3), padding=1, use_bias=False,
                          name="Conv_1")(out)
        out = self._norm(self.planes)(out)
        if self.stride != 1 or x.shape[-1] != self.planes:
            identity = nn.Conv(self.planes, (1, 1),
                               strides=(self.stride, self.stride),
                               use_bias=False, name="Conv_2")(x)
            identity = self._norm(self.planes)(identity)
        return nn.relu(out + identity)


class ResNetGN(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 100
    channels_per_group: int = 32
    small_images: bool = True

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = nn.GroupNorm(num_groups=max(1, 64 // self.channels_per_group))
        if self.small_images:
            x = nn.Conv(64, (3, 3), padding=1, use_bias=False)(x)
            x = nn.relu(norm(x))
        else:
            x = nn.Conv(64, (7, 7), strides=(2, 2), padding=3,
                        use_bias=False)(x)
            x = nn.relu(norm(x))
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for stage, blocks in enumerate(self.stage_sizes):
            planes = 64 * (2 ** stage)
            for b in range(blocks):
                stride = 2 if (stage > 0 and b == 0) else 1
                x = GNBasicBlock(planes, stride,
                                 self.channels_per_group)(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


def resnet18_gn(num_classes: int = 100, channels_per_group: int = 32,
                small_images: bool = True) -> ResNetGN:
    return ResNetGN(stage_sizes=[2, 2, 2, 2], num_classes=num_classes,
                    channels_per_group=channels_per_group,
                    small_images=small_images)
