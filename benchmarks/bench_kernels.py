"""Kernel microbenches: wall time of the Pallas kernels (interpret mode on
CPU -- correctness-path timing, NOT TPU perf) + allclose deltas vs the
pure-jnp oracles.  TPU perf is assessed structurally via the planner and
the roofline (see EXPERIMENTS.md)."""

import jax
import numpy as np

from benchmarks.common import row, timed
from repro.kernels import ops, ref
from repro.kernels.squash import squash

KEY = jax.random.PRNGKey(0)


def main() -> list[str]:
    rows = []
    x = jax.random.normal(KEY, (4096, 256))
    (s, us) = timed(lambda: np.asarray(squash(
        x, interpret=ops.should_interpret())), repeats=2)
    err = np.abs(s - np.asarray(ref.squash(x))).max()
    rows.append(row("kernels.squash_4kx256", us, f"maxerr={err:.2e}"))

    wgt = 0.1 * jax.random.normal(KEY, (1024,))
    xr = jax.random.normal(KEY, (2048, 1024))
    (y, us) = timed(lambda: np.asarray(ops.rmsnorm(xr, wgt)), repeats=2)
    err = np.abs(y - np.asarray(ref.rmsnorm(xr, wgt))).max()
    rows.append(row("kernels.rmsnorm_2kx1k", us, f"maxerr={err:.2e}"))

    q = jax.random.normal(KEY, (1, 4, 256, 64))
    k = jax.random.normal(KEY, (1, 4, 256, 64))
    v2 = jax.random.normal(KEY, (1, 4, 256, 64))
    (o, us) = timed(lambda: np.asarray(
        ops.flash_attention(q, k, v2, causal=True)), repeats=2)
    err = np.abs(o - np.asarray(ref.attention(q, k, v2, causal=True))).max()
    rows.append(row("kernels.flash_attn_256", us, f"maxerr={err:.2e}"))
    return rows


if __name__ == "__main__":
    main()
