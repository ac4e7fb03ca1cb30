"""CapsuleNet on CIFAR-10 as Sabour et al. 2017 (arXiv:1710.09829,
section 7) trained it: 24x24x3 patches, Conv1 9x9 3->256 (16x16x256),
PrimaryCaps 9x9 stride 2 with 64 capsule types of 8D on a 4x4 grid
(1,024 capsules), ClassCaps routing to 10 x 16D with 3 iterations, the
MNIST decoder widened to 24*24*3 outputs, and the paper's
"none-of-the-above" category in the routing softmax
(``CapsNetConfig.none_of_the_above``).  14,369,472 parameters.
Selectable via ``--arch capsnet-cifar10-sabour``.
"""

from repro.core.capsnet import CapsNetConfig


def config() -> CapsNetConfig:
    return CapsNetConfig(
        image_hw=24,
        in_channels=3,
        conv1_channels=256,
        conv1_kernel=9,
        pc_kernel=9,
        pc_stride=2,
        num_primary_groups=64,
        primary_dim=8,
        num_classes=10,
        class_dim=16,
        routing_iters=3,
        decoder_hidden=(512, 1024),
        none_of_the_above=True,
    )


def smoke_config() -> CapsNetConfig:
    """Same topology and the none-of-the-above category, toy widths for
    CI."""
    return CapsNetConfig(
        image_hw=12,
        in_channels=3,
        conv1_channels=32,
        conv1_kernel=5,
        pc_kernel=3,
        pc_stride=2,
        num_primary_groups=4,
        primary_dim=4,
        class_dim=8,
        decoder_hidden=(32, 64),
        none_of_the_above=True,
    )
