"""The comparisons that decide ``correct``, and their limits.

Each compared number has a limit of its own, kept in the cell's
``workloads/<cell>.json`` under ``limits``; PERF.md gives the readings
each limit was set from.  A run is correct when every number is present
and at or under its limit.
"""

from __future__ import annotations

import numpy as np

# A served prediction must match the reference's wherever the reference's
# two longest capsules differ by more than this (random weights make
# near-ties that rounding may order either way).
PRED_MARGIN = 1e-4


def compare_serve(answers: dict, ref_lengths: dict) -> dict:
    """``answers``: rid -> (status, lengths, pred) of the sampled
    requests; ``ref_lengths``: rid -> the reference's lengths.

    - ``missing``: sampled requests that never got an answer;
    - ``len_diff``: widest |lengths - reference| over the answered ones;
    - ``pred_off``: answered predictions that differ from the
      reference's argmax where it is not a near-tie."""
    missing, worst, off = 0, 0.0, 0
    for rid, (status, lengths, pred) in answers.items():
        if status != "ok":
            missing += 1
            continue
        want = np.asarray(ref_lengths[rid], np.float64)
        worst = max(worst, float(np.max(np.abs(
            np.asarray(lengths, np.float64) - want))))
        top2 = np.sort(want)[-2:]
        if top2[1] - top2[0] > PRED_MARGIN and pred != int(np.argmax(want)):
            off += 1
    return {"missing": missing, "len_diff": worst, "pred_off": off}


def _leaf_gaps(prog: dict, ref: dict, keys) -> list[float]:
    """Each leaf's |norm_prog - norm_ref|, against the larger of that
    leaf's reference norm and the median leaf's."""
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    return [abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys]


def moved_leaves(grad_norms: dict) -> list[str]:
    """Leaves whose reference gradient is not nought to rounding: at
    least a thousandth of the median leaf's."""
    med = float(np.median(list(grad_norms.values())))
    return sorted(k for k, g in grad_norms.items() if g >= 1e-3 * med)


def compare_train(prog: dict, ref: dict) -> dict:
    """Readings of the first steps, each a dict with ``losses`` (per
    step), ``grad`` (per-leaf norm of the first clipped gradient) and
    ``change`` (per-leaf norm of the parameters' change over the
    steps); ``ref`` also has ``raw_grad`` (per-leaf norm of the first
    unclipped gradient), which picks the leaves compared by change.

    - ``loss_rel``: worst step's |loss - reference| / |reference|;
    - ``grad_gap``: worst leaf's gap of first-gradient norms;
    - ``step_gap_med``: the median moved leaf's gap of parameter-change
      norms;
    - ``step_gap_max``: the worst moved leaf's, which is not compared.
      AdamW divides each element's step by its gradient's root mean
      square plus 1e-8, so a leaf with many elements whose gradient
      sits near 1e-8 (the decoder's, under the reconstruction loss's
      0.0005 weight) turns rounding into step size: on some seeds the
      worst leaf reads 2e-4 while every gradient norm agrees to 3e-7."""
    loss_rel = float(np.max([
        abs(p - r) / abs(r)
        for p, r in zip(prog["losses"], ref["losses"], strict=True)]))
    steps = _leaf_gaps(prog["change"], ref["change"],
                       moved_leaves(ref["raw_grad"]))
    return {"loss_rel": loss_rel,
            "grad_gap": float(np.max(_leaf_gaps(prog["grad"], ref["grad"],
                                                ref["grad"]))),
            "step_gap_med": float(np.median(steps)),
            "step_gap_max": float(np.max(steps))}


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over every limit; readings
    with no limit are not compared."""
    checks = {k: {"value": readings.get(k), "limit": lim}
              for k, lim in limits.items()}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
