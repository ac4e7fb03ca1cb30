"""A training cell: AdamW steps through the training loop's own step.

Set-up builds one training loop (its plan and its jitted step), makes
the parameters and a pool of distinct batches from the seed, and drives
the loop's step through its first ``check_steps`` steps; those steps
compile it and give the readings the check compares.  The same state
then runs on in the window, one step after another, each followed by
the loop's own per-step read of the loss.  After the window the state is
freed and the plain reference takes the same first steps from the seed.
"""

from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import checks


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


@jax.jit
def _change_norms(new, old):
    return {k: jnp.sqrt(jnp.sum(jnp.square(new[k] - old[k]))) for k in new}


def _host(tree) -> dict:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def _opt(tr: dict) -> dict:
    return dict(tr["optimizer"])


def run(cell, seed: int, seconds: float, *, ref, prog, span, counter,
        gc_pauses, mark_setup_done, memory_peak) -> dict:
    s, tr, wl = cell.sizes, cell.traffic, cell.params
    batch, n_check, opt = wl["batch"], tr["check_steps"], _opt(tr)
    loop = prog.make_train_loop(s, batch, opt)
    params = ref.init_params(seed, s)
    batches = [dict(zip(("images", "labels"),
                        ref.train_batch(seed, s, batch, i)))
               for i in range(tr["batch_pool"])]
    state = {"params": params, "opt": prog.init_opt_state(params)}
    start = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), params)
    del params
    losses, grad = [], None
    for i in range(n_check):
        state, metrics = loop._run_step(state, batches[i])
        losses.append(float(jax.device_get(metrics["loss"])))
        if i == 0:   # the first clipped gradient, as AdamW's m holds it
            grad = {k: v / (1 - opt["b1"])
                    for k, v in _host(_leaf_norms(state["opt"]["m"])).items()}
    change = _host(_change_norms(state["params"], start))
    del start
    readings_prog = {"losses": losses, "grad": grad, "change": change}
    gc.collect()
    mark_setup_done()
    counter.active = gc_pauses.active = True
    steps, failed, k = 0, 0, n_check
    with span("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with span("train.step"):
                state, metrics = loop._run_step(
                    state, batches[k % len(batches)])
            with span("train.sync"):
                loss = float(jax.device_get(metrics["loss"]))
            failed += not np.isfinite(loss)
            steps += 1
            k += 1
        window = time.perf_counter() - t0
    counter.active = gc_pauses.active = False
    peak = memory_peak()
    del state, metrics, loop, batches
    gc.collect()
    readings_ref = reference_readings(ref, s, seed, batch, n_check, opt,
                                      "highest")
    return {"run": {"steps": steps, "batch": batch, "window_s": window,
                    "memory_peak_bytes": peak},
            "readings": checks.compare_train(readings_prog, readings_ref),
            "attempted": steps, "failed": failed,
            "end_to_end": {"train_step_ms": 1e3 * window / steps}}


def reference_readings(ref, s: dict, seed: int, batch: int, n_steps: int,
                       opt: dict, precision: str, keep: int | None = None
                       ) -> dict:
    """The reference's first ``n_steps`` AdamW steps from the seed:
    losses, the first (clipped and raw) gradient's leaf norms, and the
    leaf norms of the parameters' change.  ``keep`` rows of each batch
    only, where given (a planted fault: the rest of the batch left out,
    the loss the mean over the rows kept)."""
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, s=s, precision=precision)))
    params = ref.init_params(seed, s)
    start = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    opt_items = tuple(sorted(opt.items()))
    out = {"losses": []}
    for step in range(1, n_steps + 1):
        images, labels = ref.train_batch(seed, s, batch, step - 1)
        images, labels = images[:keep], labels[:keep]
        loss, grads = grad_fn(params, images, labels)
        out["losses"].append(float(loss))
        if step == 1:
            out["raw_grad"] = _host(_leaf_norms(grads))
        params, m, v, clipped = ref.adamw_step(
            params, grads, m, v, jnp.float32(step),
            jnp.float32(ref.adamw_lr(step, opt)), opt_items)
        if step == 1:
            out["grad"] = _host(_leaf_norms(clipped))
        del grads, clipped
    out["change"] = _host(_change_norms(params, start))
    return out


def control(cell, seed: int, seconds: float = 0.0, *, ref) -> dict:
    """The control's readings: the reference at the next precision down
    in the program's place (its first steps need no window)."""
    s, tr, wl = cell.sizes, cell.traffic, cell.params
    opt, n, batch = _opt(tr), tr["check_steps"], wl["batch"]
    low = reference_readings(ref, s, seed, batch, n, opt, "high")
    want = reference_readings(ref, s, seed, batch, n, opt, "highest")
    return checks.compare_train(low, want)


def half_batch(cell, seed: int, seconds: float = 0.0, *,
               ref) -> dict | None:
    """The readings of a planted fault, in the reference put in the
    program's place: half of each batch left out.  None at batch 1."""
    s, tr, wl = cell.sizes, cell.traffic, cell.params
    opt, n, batch = _opt(tr), tr["check_steps"], wl["batch"]
    if batch < 2:
        return None
    low = reference_readings(ref, s, seed, batch, n, opt, "highest",
                             keep=batch // 2)
    want = reference_readings(ref, s, seed, batch, n, opt, "highest")
    return checks.compare_train(low, want)


PLANTED = {"half_batch": half_batch}
