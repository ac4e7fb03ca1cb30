"""A DEEP residual capsule stack at CIFAR-10's 32x32x3 input.

This repository's own network, published nowhere: Sabour et al.'s
Conv1 and PrimaryCaps widths on full 32x32x3 images, with three
reversible MoCapsNet-style ``ResCapsBlock``s between PrimaryCaps and
ClassCaps (residual routing blocks are what make *depth* affordable).
It exercises the layer-graph plan compiler: per-layer fused megakernel
ops, per-instance PMU phases, and the flat-in-depth reversible backward.
Sabour et al.'s own CIFAR-10 network (24x24x3 patches, 64 primary
capsule types, a none-of-the-above routing category) is
``capsnet-cifar10-sabour``.  Selectable via ``--arch capsnet-cifar10``.
"""

from repro.core.capsnet import CapsNetConfig, ResCapsBlock


def config() -> CapsNetConfig:
    return CapsNetConfig(
        image_hw=32,
        in_channels=3,
        conv1_channels=256,
        conv1_kernel=9,
        pc_kernel=9,
        pc_stride=2,
        num_primary_groups=32,
        primary_dim=8,
        num_classes=10,
        class_dim=16,
        decoder_hidden=(512, 1024),
        caps_layers=(ResCapsBlock(), ResCapsBlock(), ResCapsBlock()),
    )


def smoke_config() -> CapsNetConfig:
    """Same topology (3 reversible blocks), toy widths for CI."""
    return CapsNetConfig(
        image_hw=16,
        in_channels=3,
        conv1_channels=32,
        conv1_kernel=5,
        pc_kernel=3,
        pc_stride=2,
        num_primary_groups=4,
        primary_dim=4,
        class_dim=8,
        decoder_hidden=(32, 64),
        caps_layers=(ResCapsBlock(), ResCapsBlock(), ResCapsBlock()),
    )
