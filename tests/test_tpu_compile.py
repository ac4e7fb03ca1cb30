"""Mosaic compile-only checks: the main-path Pallas kernels at
capsnet-mnist's published widths (batch 8; the pipe kernel also at the
benchmark cells' 16), compiled for a described
v5e chip with ``interpret=False``.  Nothing runs; a kernel the TPU
compiler refuses (a misaligned slice, an over-budget VMEM allocation)
fails here without a chip.

The topology is described only inside a fixture: only one process at a
time may load the TPU library, so describing it while modules are
imported would break multi-worker collection.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import (BWD_SUFFIX, FUSED_NAME, PIPE_NAME,
                                 compile_plan)
from repro.kernels.conv_im2col import conv2d_im2col
from repro.kernels.primary_routing import (primary_caps_routing,
                                           primary_caps_routing_nota)
from repro.kernels.votes_routing import votes_routing, votes_routing_nota

CFG = CapsNetConfig()        # capsnet-mnist published widths
BATCH = 8
CELL_BATCH = 16              # the benchmark cells' slots and train batch
# Sabour et al.'s CIFAR-10 network (none-of-the-above routing) at the
# batch of its benchmark cells.
SABOUR = get_config("capsnet-cifar10-sabour")
SABOUR_BATCH = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a compile for a described chip is written to it but cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text          # a Mosaic kernel, compiled
    return text


def _grad(fn, n_args):
    return jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=tuple(range(n_args)))


@pytest.mark.parametrize("layer", ["Conv1", "PrimaryCaps"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_conv_im2col_compiles(one_chip, layer, direction):
    op = compile_plan(CFG, batch=BATCH, train=True).op(layer)
    if layer == "Conv1":
        shapes = ((BATCH, CFG.image_hw, CFG.image_hw, CFG.in_channels),
                  (CFG.conv1_kernel, CFG.conv1_kernel, CFG.in_channels,
                   CFG.conv1_channels), (CFG.conv1_channels,))
        kw = dict(stride=1, epilogue="relu")
    else:
        shapes = ((BATCH, CFG.conv1_out, CFG.conv1_out, CFG.conv1_channels),
                  (CFG.pc_kernel, CFG.pc_kernel, CFG.conv1_channels,
                   CFG.pc_channels), (CFG.pc_channels,))
        kw = dict(stride=CFG.pc_stride, epilogue="squash",
                  squash_dim=CFG.primary_dim)

    def conv(x, w, b):
        return conv2d_im2col(x, w, b, block_m=op.block.block_m,
                             block_k=op.block.block_k,
                             block_n=op.block.block_n, interpret=False,
                             **kw)

    _compile(conv if direction == "fwd" else _grad(conv, 3), one_chip,
             *shapes)


@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_votes_routing_compiles(one_chip, mode, direction):
    jd = CFG.num_classes * CFG.class_dim

    def route(u, w):
        return votes_routing(u, w, iters=CFG.routing_iters,
                             num_classes=CFG.num_classes, mode=mode,
                             block_i=128, bwd_mode=mode, interpret=False)

    _compile(route if direction == "fwd" else _grad(route, 2), one_chip,
             (BATCH, CFG.num_primary, CFG.primary_dim),
             (CFG.num_primary, jd, CFG.primary_dim))


@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_votes_routing_class_lanes_compiles(one_chip, mode, direction):
    """The output-capsules-on-lanes layout at a capsnet-cifar10 ResCaps
    half's widths (1024 capsules routed into 1024 x 8D), on the schedule
    its train plan picks; resident at an i-tile whose votes fit."""
    cfg = get_config("capsnet-cifar10")
    lay = cfg.routing_stack()[0]
    op = compile_plan(cfg, batch=1, train=True).op(
        lay.name + ("" if direction == "fwd" else BWD_SUFFIX))
    assert op.lanes == "classes" and op.mode == "streamed"
    in_caps = lay.in_caps if mode == "streamed" else 64

    def route(u, w):
        return votes_routing(u, w, iters=lay.iters, num_classes=lay.num_caps,
                             mode=mode, block_i=op.block_i, lanes="classes",
                             interpret=False)

    _compile(route if direction == "fwd" else _grad(route, 2), one_chip,
             (1, in_caps, lay.in_dim), (in_caps, lay.jd, lay.in_dim))


@pytest.mark.parametrize("batch", [BATCH, CELL_BATCH])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_primary_routing_compiles(one_chip, batch, direction):
    """The pipe kernel and its VJP on the train plan's schedule, at batch 8
    and at the benchmark cells' 16 (streamed, where the produce phase's
    [B*P, N] scratch and patch rows are largest)."""
    plan = compile_plan(CFG, batch=batch, pipeline=True, train=True)
    op, bwd = plan.op(PIPE_NAME), plan.op(FUSED_NAME + BWD_SUFFIX)
    jd = CFG.num_classes * CFG.class_dim

    def pipe(x, w_pc, b_pc, w_cc):
        return primary_caps_routing(
            x, w_pc, b_pc, w_cc, stride=CFG.pc_stride,
            iters=CFG.routing_iters, num_classes=CFG.num_classes,
            mode=op.mode, block_i=op.block_i, block_k=op.block_k,
            bwd_mode=bwd.mode, bwd_block_i=bwd.block_i, bwd_lanes=bwd.lanes,
            conv_block_m=op.block.block_m, conv_block_k=op.block.block_k,
            conv_block_n=op.block.block_n, interpret=False)

    _compile(pipe if direction == "fwd" else _grad(pipe, 4), one_chip,
             (batch, CFG.conv1_out, CFG.conv1_out, CFG.conv1_channels),
             (CFG.pc_kernel, CFG.pc_kernel, CFG.conv1_channels,
              CFG.pc_channels), (CFG.pc_channels,),
             (CFG.num_primary, jd, CFG.primary_dim))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_primary_routing_nota_compiles(one_chip, direction):
    """The pipe kernel and its VJP with the none-of-the-above category at
    the CIFAR-10 network's published widths, on its train plan's schedule
    (the pipelined pair at the VMEM budget's edge); the trace names the
    calls apart from the default path's."""
    plan = compile_plan(SABOUR, batch=SABOUR_BATCH, pipeline=True,
                        train=True)
    op, bwd = plan.op(PIPE_NAME), plan.op(FUSED_NAME + BWD_SUFFIX)
    lay = SABOUR.routing_stack()[0]

    def pipe(x, w_pc, b_pc, w_cc):
        return primary_caps_routing_nota(
            x, w_pc, b_pc, w_cc, stride=SABOUR.pc_stride, iters=lay.iters,
            num_classes=lay.num_caps, mode=op.mode, block_i=op.block_i,
            block_k=op.block_k, bwd_mode=bwd.mode, bwd_block_i=bwd.block_i,
            bwd_lanes=bwd.lanes, conv_block_m=op.block.block_m,
            conv_block_k=op.block.block_k, conv_block_n=op.block.block_n,
            interpret=False)

    text = _compile(pipe if direction == "fwd" else _grad(pipe, 4), one_chip,
                    (SABOUR_BATCH, SABOUR.conv1_out, SABOUR.conv1_out,
                     SABOUR.conv1_channels),
                    (SABOUR.pc_kernel, SABOUR.pc_kernel,
                     SABOUR.conv1_channels, SABOUR.pc_channels),
                    (SABOUR.pc_channels,),
                    (lay.in_caps, lay.jd, lay.in_dim))
    assert "primary_caps_routing_nota" in text
    if direction == "bwd":
        assert "transpose_jvp_jit_primary_caps_routing_nota" in text


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_votes_routing_nota_compiles(one_chip, direction):
    """The fused routing kernel with the category at the CIFAR-10
    network's widths, on its per-op train plan's schedules."""
    plan = compile_plan(SABOUR, batch=SABOUR_BATCH, train=True)
    op = plan.op(FUSED_NAME + ("" if direction == "fwd" else BWD_SUFFIX))
    lay = SABOUR.routing_stack()[0]

    def route(u, w):
        return votes_routing_nota(u, w, iters=lay.iters,
                                  num_classes=lay.num_caps, mode=op.mode,
                                  block_i=op.block_i, lanes=op.lanes,
                                  bwd_mode=op.mode, bwd_block_i=op.block_i,
                                  bwd_lanes=op.lanes, interpret=False)

    text = _compile(route if direction == "fwd" else _grad(route, 2),
                    one_chip, (SABOUR_BATCH, lay.in_caps, lay.in_dim),
                    (lay.in_caps, lay.jd, lay.in_dim))
    assert "votes_routing_nota" in text
