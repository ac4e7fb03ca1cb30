"""The chip path's guards: interpret mode follows the platform, and
``chip_smoke.py`` refuses to run anywhere but a TPU -- while its phases
still run end to end here at a tiny size with the kernels interpreted."""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import capsnet_mnist
from repro.core import capsnet
from repro.kernels import ops
from repro.train.data import DataConfig, mnist_batch

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_should_interpret_follows_platform(monkeypatch):
    assert ops.should_interpret() is True              # the CPU backend
    assert ops.should_interpret(False) is False         # explicit wins
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.should_interpret() is False
    assert ops.should_interpret(True) is True


def test_chip_smoke_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_serve_and_train_paths_do_not_import_dryrun():
    """``repro.launch.dryrun`` forces 512 host devices through XLA_FLAGS
    when imported; the serve and train entry points must never pull it
    in (the chip smoke asserts the same on the chip)."""
    code = ("import sys, repro.serve.capsule, repro.train.capsnet_loop, "
            "repro.kernels.ops; "
            "sys.exit('repro.launch.dryrun' in sys.modules)")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src")}
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_rejects_backward_fallback(chip_smoke):
    """A backward that cannot be planned falls back to the forward
    schedule with a RuntimeWarning; the smoke's warning check must turn
    that into a failure."""
    import warnings

    import jax.numpy as jnp

    from repro.core import execplan
    cfg = capsnet.CapsNetConfig(image_hw=8, conv1_channels=8, conv1_kernel=3,
                                pc_kernel=3, num_primary_groups=4,
                                primary_dim=4, num_classes=3, class_dim=8,
                                use_decoder=False)
    lay = cfg.routing_stack()[0]                # 16 x 4D -> 3 x 8D
    floor = execplan._fused_streamed_bwd_vmem(
        1, lay.in_caps, execplan._min_block_i(lay.in_caps), lay.in_dim,
        lay.jd, lay.num_caps, lay.iters)
    plan = execplan.compile_plan(cfg, batch=1, vmem_budget=floor - 1)
    ops._warn_bwd_fallback_once.cache_clear()
    u = jnp.ones((1, 16, 4), jnp.float32)
    w = jnp.full((16, 3 * 8, 4), 0.01, jnp.float32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        jax.grad(lambda x: ops.votes_routing(x, w, lay, plan).sum())(u)
    assert any("no feasible" in str(c.message) for c in caught)
    with pytest.raises(chip_smoke.SmokeFailure, match="RuntimeWarning"):
        chip_smoke.check_no_kernel_warnings(caught)
    chip_smoke.check_no_kernel_warnings([])


def test_chip_smoke_phases_at_tiny_size(chip_smoke, tmp_path, monkeypatch):
    """The serve and train phases at smoke widths, kernels interpreted:
    the same checks (statuses, counters, reference parity, finite
    losses) the chip run makes."""
    monkeypatch.setattr(chip_smoke, "CKPT_DIR", tmp_path / "ckpt")
    cfg = capsnet_mnist.smoke_config()
    params = capsnet.init_params(jax.random.PRNGKey(0), cfg)
    images = mnist_batch(DataConfig(kind="mnist", global_batch=16), 0,
                         image_hw=cfg.image_hw)["images"]
    assert chip_smoke.serve_phase(cfg, params, images, slots=4,
                                  requests=16) <= chip_smoke.LENGTH_ATOL
    assert chip_smoke.train_phase(cfg, 0, steps=2,
                                  want_batch=8) <= chip_smoke.LOSS_RTOL


def test_chip_smoke_patches_phase_at_tiny_size(chip_smoke, monkeypatch):
    """The bitwise patch check passes on the real formulations and fails
    on patches that differ from the plain extraction in one entry."""
    from repro.kernels import conv_im2col
    cfg = capsnet_mnist.smoke_config()
    chip_smoke.patches_phase(cfg, 0, batch=2)
    real = conv_im2col.im2col_patches

    def off_by_one_entry(x, **kw):
        return real(x, **kw).at[0, 0, 0].add(1.0)

    monkeypatch.setattr(conv_im2col, "im2col_patches", off_by_one_entry)
    with pytest.raises(chip_smoke.SmokeFailure, match="patches"):
        chip_smoke.patches_phase(cfg, 0, batch=2)


def test_chip_smoke_wide_layer_phase_at_tiny_size(chip_smoke):
    """The wide-layer phase on a smoke-width ResCaps stack whose budget
    puts the output capsules on the lanes, kernels interpreted."""
    from repro.configs.registry import get_smoke_config
    v_diff, g_rel = chip_smoke.wide_layer_phase(
        0, cfg=get_smoke_config("capsnet-cifar10"), vmem_budget=900_000)
    assert v_diff <= chip_smoke.WIDE_ATOL
    assert g_rel <= chip_smoke.WIDE_GRAD_RTOL
