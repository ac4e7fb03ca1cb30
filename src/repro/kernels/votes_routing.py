"""Fused ClassCaps votes + routing megakernel: u_hat never touches HBM.

CapStore's central claim (Sec. 3.1) is that no routing value leaves the
chip.  A votes kernel followed by a routing kernel would write the
votes tensor ``u_hat [B, I, J*D]`` -- the single largest intermediate of
the network -- to HBM and immediately read it back, a
produce-once/consume-once round-trip dominating the traffic of the
memory-bound ClassCaps stage (CapsAcc: zero weight reuse, so bytes
moved, not FLOPs, are the lever).  This kernel computes the votes from
the u-tile and streamed ``W`` i-blocks and runs ALL routing iterations
with the routing state (logits ``b``, couplings ``c``, candidates
``s``/``v``) in VMEM scratch, so per forward only ``u [B, I, C]`` and
``W [I, J*D, C]`` are read and only ``v [B, J*D]`` is written.

The ExecutionPlan (``repro.core.execplan.plan_votes_routing``) chooses
between two schedules per configuration -- the DESCNet-style
per-configuration scratchpad decision.  Both run the grid ``(iters + 1,
num_i_blocks)`` with the routing state in scratch.  Pass ``t`` runs one
WHOLE routing iteration: while accumulating ``s_t`` from a votes block it
first folds in the logits update ``b_t = b_{t-1} + <u_hat, v_{t-1}>`` for
the same rows, against the previous pass's ``v_{t-1}`` held in VMEM
scratch.

  resident  pass 0 computes each votes block into a VMEM scratch holding
            the whole votes tensor and later passes read it back: ``W``
            and ``u`` are read exactly once.  Requires the full votes
            tensor to fit VMEM.

  streamed  every pass recomputes its votes block from a re-streamed
            ``W`` tile, so ``W`` is read ``iters + 1`` times -- the price
            of making num_primary >> VMEM configurations feasible at all.

Every kernel step holds one votes block in registers, never the whole
votes tensor.  The plan also picks which capsule axis lies on the
128-lane axis (``lanes``, see ``_CapsLanes`` / ``_ClassLanes``):

  "caps"     the input capsules I (u ``[B, C, I_pad]``, W ``[C, J, D,
             I_pad]``, logits ``[B, J, I_pad]``): dense for the narrow
             output layers of the classification heads, but ``block_i``
             is a multiple of 128 or all of I and every class vector
             ``[B, J, D, 1]`` pads one lane out to 128.

  "classes"  the output capsules J (u ``[B, I_pad, C]`` streamed one
             i-block per step like W, W ``[C, D, I_pad, J]``, logits
             ``[B, I_pad, J]``, class vectors ``[B, D, 1, J]``): dense for
             wide layers (a ResCaps half routes 1024 capsules into 1024),
             and ``block_i`` only needs to be a multiple of 8, so one W
             tile stays small however wide J*D is.

**Backward** (``jax.custom_vjp``): the cotangent of the votes, ``d u_hat``
-- as large as ``u_hat`` itself -- never touches HBM either.  The
backward kernel (grid ``(iters + 4, num_i_blocks)``, see
``_routing_bwd_kernel``) recomputes the routing iterations from the saved
``(u, W)`` residuals entirely in VMEM scratch, honoring the jnp
reference's ``stop_gradient(u_hat)`` convention (the logits updates and
every s-sum but the last iteration's are u_hat-constant under
``jax.grad``): one forward replay, one seed pass, ONE reverse pass and an
emit pass, regardless of the iteration count.  Resident keeps the
rebuilt votes in scratch (``W`` read twice); streamed recomputes them
from ``W`` on every pass (``iters + 4`` reads).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.capsnet import couplings, squash
from repro.core.execplan import LAYOUTS
from repro.core.planner import VMEM_LIMIT_BYTES

MODES = ("resident", "streamed")        # plan-chooseable schedules
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


# ---------------------------------------------------------------------------
# Kernel layouts
# ---------------------------------------------------------------------------

class _CapsLanes:
    """Input capsules I on the lanes.

    The capsule axis I is the only long axis of a classification head
    (1152 -> 10 x 16 on MNIST), so every buffer is dense under the (8,
    128) tiling (C and D sit on sublanes, B and J lead), where the [B, I,
    C] / [I, J*D, C] layouts would pad C=8 out to 128 lanes.  u stays
    resident for the run (constant block index); votes blocks are [B, J,
    D, TI], logits [B, J, I_pad], class vectors [B, J, D, 1]."""

    name = "caps"
    class_axis = 1          # J in logits / couplings blocks [B, J, TI]
    dim_axis = 2            # D in votes [B, J, D, TI] / vectors [B, J, D, 1]

    @staticmethod
    def to_kernel(u, w, j: int, i_pad: int):
        """u [B, I, C] -> [B, C, I_pad]; w [I, J*D, C] -> [C, J, D, I_pad].
        I is zero-padded up to a multiple of ``block_i``: a clamped ragged
        tail block would double-count rows under the i-reduction, while
        zero capsules add nothing to ``s``, keep uniform logits, and never
        perturb the real capsules."""
        _, i_dim, c = u.shape
        jd = w.shape[1]
        pad = (0, i_pad - i_dim)
        ut = jnp.pad(u.transpose(0, 2, 1), ((0, 0), (0, 0), pad))
        wt = jnp.pad(w.transpose(2, 1, 0).reshape(c, j, jd // j, i_dim),
                     ((0, 0), (0, 0), (0, 0), pad))
        return ut, wt

    @staticmethod
    def u_spec(bsz, c, i_pad, block_i, i_index):
        del block_i, i_index                    # resident: fetched once
        return pl.BlockSpec((bsz, c, i_pad), lambda p, ib: (0, 0, 0))

    @staticmethod
    def w_spec(c, j, d, block_i, i_index):
        return pl.BlockSpec((c, j, d, block_i),
                            lambda p, ib: (0, 0, 0, i_index(p, ib)))

    @staticmethod
    def u_block(u_ref, rows):
        return u_ref[:, :, rows]

    @staticmethod
    def vec_shape(bsz, j, d):
        return (bsz, j, d, 1)

    @staticmethod
    def to_vec(x, j: int):
        """[B, J*D] -> [B, J, D, 1]."""
        return x.reshape(x.shape[0], j, x.shape[1] // j, 1)

    @staticmethod
    def from_vec(v):
        return v.reshape(v.shape[0], -1)

    @staticmethod
    def logits_shape(bsz, j, i_pad):
        return (bsz, j, i_pad)

    @staticmethod
    def votes_shape(bsz, j, d, i_pad):
        return (bsz, j, d, i_pad)

    @staticmethod
    def b_idx(rows):
        return (slice(None), slice(None), rows)

    @staticmethod
    def votes_idx(rows):
        return (slice(None), slice(None), slice(None), rows)

    @staticmethod
    def votes(u, w):
        """u [B, C, TI], w [C, J, D, TI] -> votes [B, J, D, TI] (fp32).

        The contraction is only C deep and W differs per capsule, so
        there is no matmul shape here: C broadcast multiply-adds on the
        vector unit."""
        bsz, c, ti = u.shape
        u = u.astype(jnp.float32)
        w = w.astype(jnp.float32)
        acc = u[:, 0:1, :].reshape(bsz, 1, 1, ti) * w[0][None]
        for k in range(1, c):
            acc = acc + u[:, k:k + 1, :].reshape(bsz, 1, 1, ti) * w[k][None]
        return acc

    @staticmethod
    def spread(c):
        """Couplings [B, J, TI] onto the votes layout [B, J, 1, TI]."""
        return c[:, :, None, :]

    @staticmethod
    def weighted_sum(c, uh):
        """sum_i c[b, j, i] * uh[b, j, d, i] -> [B, J, D, 1]."""
        return jnp.sum(_CapsLanes.spread(c) * uh, axis=3, keepdims=True)

    @staticmethod
    def agreement(uh, v):
        """<u_hat, v> over the class dim: [B, J, D, TI] x [B, J, D, 1] ->
        [B, J, TI]."""
        return jnp.sum(uh * v, axis=2)

    @staticmethod
    def grad_specs(bsz, c, j, d, block_i, e_index):
        return [pl.BlockSpec((bsz, c, block_i),
                             lambda p, ib: (0, 0, e_index(p, ib))),
                pl.BlockSpec((c, j, d, block_i),
                             lambda p, ib: (0, 0, 0, e_index(p, ib)))]

    @staticmethod
    def grad_shapes(bsz, c, j, d, i_pad):
        return [(bsz, c, i_pad), (c, j, d, i_pad)]

    @staticmethod
    def emit(duh, w_ref, u, du_ref, dw_ref):
        """du [B, C, TI] and dW [C, J, D, TI] from d u_hat [B, J, D, TI]."""
        w = w_ref[...].astype(jnp.float32)
        u = u.astype(jnp.float32)
        bsz, c, ti = u.shape
        for k in range(c):
            du_ref[:, k:k + 1, :] = jnp.sum(
                jnp.sum(duh * w[k][None], axis=2, keepdims=True),
                axis=1).astype(du_ref.dtype)                  # [B, 1, TI]
            dw_ref[k] = jnp.sum(
                duh * u[:, k:k + 1, :].reshape(bsz, 1, 1, ti),
                axis=0).astype(dw_ref.dtype)                  # [J, D, TI]

    @staticmethod
    def from_grads(du, dw, i_dim: int):
        c = du.shape[1]
        du = du.transpose(0, 2, 1)[:, :i_dim]
        dw = dw.reshape(c, -1, dw.shape[-1]).transpose(2, 1, 0)[:i_dim]
        return du, dw


class _ClassLanes:
    """Output capsules J on the lanes.

    For a wide routing layer (J*D = 8192 on a ResCaps half) one
    128-capsule W tile of the caps-on-lanes layout is tens of MiB and
    every class vector pads 128x; with J on the lanes the class vectors
    [B, D, 1, J] and logits [B, I_pad, J] are dense, I sits on sublanes
    (``block_i`` a multiple of 8), and a W tile [C, D, TI, J] stays a few
    MiB.  u keeps its natural [B, I, C] layout and streams one i-block
    per step alongside W, so a capsule's C components are lane columns
    that broadcast across the J lanes."""

    name = "classes"
    class_axis = 2          # J in logits / couplings blocks [B, TI, J]
    dim_axis = 1            # D in votes [B, D, TI, J] / vectors [B, D, 1, J]

    @staticmethod
    def to_kernel(u, w, j: int, i_pad: int):
        """u [B, I, C] -> [B, I_pad, C]; w [I, J*D, C] -> [C, D, I_pad, J]
        (zero capsules pad I, as in the caps-on-lanes layout)."""
        _, i_dim, c = u.shape
        jd = w.shape[1]
        pad = (0, i_pad - i_dim)
        ut = jnp.pad(u, ((0, 0), pad, (0, 0)))
        wt = jnp.pad(w.reshape(i_dim, j, jd // j, c).transpose(3, 2, 0, 1),
                     ((0, 0), (0, 0), pad, (0, 0)))
        return ut, wt

    @staticmethod
    def u_spec(bsz, c, i_pad, block_i, i_index):
        del i_pad
        return pl.BlockSpec((bsz, block_i, c),
                            lambda p, ib: (0, i_index(p, ib), 0))

    @staticmethod
    def w_spec(c, j, d, block_i, i_index):
        return pl.BlockSpec((c, d, block_i, j),
                            lambda p, ib: (0, 0, i_index(p, ib), 0))

    @staticmethod
    def u_block(u_ref, rows):
        del rows                                # the block IS the i-tile
        return u_ref[...]

    @staticmethod
    def vec_shape(bsz, j, d):
        return (bsz, d, 1, j)

    @staticmethod
    def to_vec(x, j: int):
        """[B, J*D] -> [B, D, 1, J]."""
        bsz, jd = x.shape
        return x.reshape(bsz, j, jd // j).transpose(0, 2, 1)[:, :, None, :]

    @staticmethod
    def from_vec(v):
        bsz, d, _, j = v.shape
        return v.reshape(bsz, d, j).transpose(0, 2, 1).reshape(bsz, -1)

    @staticmethod
    def logits_shape(bsz, j, i_pad):
        return (bsz, i_pad, j)

    @staticmethod
    def votes_shape(bsz, j, d, i_pad):
        return (bsz, d, i_pad, j)

    @staticmethod
    def b_idx(rows):
        return (slice(None), rows, slice(None))

    @staticmethod
    def votes_idx(rows):
        return (slice(None), slice(None), rows, slice(None))

    @staticmethod
    def votes(u, w):
        """u [B, TI, C], w [C, D, TI, J] -> votes [B, D, TI, J] (fp32):
        C broadcast multiply-adds, each u lane column spread over J."""
        c = u.shape[2]
        u = u.astype(jnp.float32)
        w = w.astype(jnp.float32)
        acc = _ClassLanes.u_col(u, 0) * w[0][None]
        for k in range(1, c):
            acc = acc + _ClassLanes.u_col(u, k) * w[k][None]
        return acc

    @staticmethod
    def u_col(u, k: int):
        """Component ``k`` of a u block [B, TI, C] as [B, 1, TI, 1]."""
        bsz, ti, _ = u.shape
        return jax.lax.slice_in_dim(u, k, k + 1, axis=2).reshape(
            bsz, 1, ti, 1)

    @staticmethod
    def spread(c):
        """Couplings [B, TI, J] onto the votes layout [B, 1, TI, J]."""
        return c.reshape(c.shape[0], 1, *c.shape[1:])

    @staticmethod
    def weighted_sum(c, uh):
        """sum_i c[b, i, j] * uh[b, d, i, j] -> [B, D, 1, J]."""
        return jnp.sum(_ClassLanes.spread(c) * uh, axis=2, keepdims=True)

    @staticmethod
    def agreement(uh, v):
        """<u_hat, v> over the class dim: [B, D, TI, J] x [B, D, 1, J] ->
        [B, TI, J]."""
        return jnp.sum(uh * v, axis=1)

    @staticmethod
    def grad_specs(bsz, c, j, d, block_i, e_index):
        return [pl.BlockSpec((c, bsz, block_i, 1),
                             lambda p, ib: (0, 0, e_index(p, ib), 0)),
                pl.BlockSpec((c, d, block_i, j),
                             lambda p, ib: (0, 0, e_index(p, ib), 0))]

    @staticmethod
    def grad_shapes(bsz, c, j, d, i_pad):
        return [(c, bsz, i_pad, 1), (c, d, i_pad, j)]

    @staticmethod
    def emit(duh, w_ref, u, du_ref, dw_ref):
        """du [C, B, TI, 1] and dW [C, D, TI, J] from d u_hat [B, D, TI,
        J]: per capsule component, a lane reduction and a batch sum."""
        w = w_ref[...].astype(jnp.float32)
        u = u.astype(jnp.float32)
        for k in range(u.shape[2]):
            du_ref[k] = jnp.sum(
                jnp.sum(duh * w[k][None], axis=1), axis=-1,
                keepdims=True).astype(du_ref.dtype)           # [B, TI, 1]
            dw_ref[k] = jnp.sum(duh * _ClassLanes.u_col(u, k),
                                axis=0).astype(dw_ref.dtype)  # [D, TI, J]

    @staticmethod
    def from_grads(du, dw, i_dim: int):
        c, _, i_pad, _ = dw.shape
        du = du.reshape(c, -1, i_pad).transpose(1, 2, 0)[:, :i_dim]
        dw = dw.transpose(2, 3, 1, 0)[:i_dim]
        return du, dw.reshape(dw.shape[0], -1, c)


_LAYOUT = {"caps": _CapsLanes, "classes": _ClassLanes}


def _couplings(lay, b, nota: bool):
    """Routing couplings of a logits block over the classes
    (``capsnet.couplings``): a softmax, or with ``nota`` the
    none-of-the-above category's, whose one zero logit per input capsule
    is counted once along the class axis, whichever axis the layout puts
    on the lanes."""
    return couplings(b, axis=lay.class_axis, nota=nota)


def _squash_caps(lay, s):
    """Squash class capsules over their D axis."""
    return squash(s, axis=lay.dim_axis)


def _rows(ib, block_i: int):
    return pl.ds(pl.multiple_of(ib * block_i, block_i), block_i)


def _route_block(lay, p, ib, uh4, rows, b_scr, s_scr, v_scr, o_ref, r_ref,
                 *, n_passes: int, n_blocks: int, nota: bool):
    """One (pass, i-block) step of the fused single-stream routing.

    Pass ``t`` runs one WHOLE routing iteration: before accumulating
    ``s_t`` from this block's votes it folds in the logits update ``b_t =
    b_{t-1} + <u_hat, v_{t-1}>`` for the same rows against the previous
    pass's ``v`` in scratch (pass 0 starts from zero logits).  The last
    pass is the readout; ``r_ref`` (optional) is a residual added to it
    just before the store -- ``v_scr`` itself stays pure v."""
    b_rows = lay.b_idx(rows)

    @pl.when((p == 0) & (ib == 0))
    def _():
        b_scr[...] = jnp.zeros_like(b_scr)

    @pl.when(p > 0)
    def _():
        b_scr[b_rows] += lay.agreement(uh4, v_scr[...])

    @pl.when(ib == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s_scr[...] += lay.weighted_sum(_couplings(lay, b_scr[b_rows], nota),
                                   uh4)

    @pl.when(ib == n_blocks - 1)
    def _():
        v_scr[...] = _squash_caps(lay, s_scr[...])

        @pl.when(p == n_passes - 1)
        def _():
            out = v_scr[...]
            if r_ref is not None:
                out = out + r_ref[...].astype(jnp.float32)
            o_ref[...] = out.astype(o_ref.dtype)


def _routing_kernel(u_ref, w_ref, *refs, lanes: str, n_passes: int,
                    n_blocks: int, block_i: int, resident: bool,
                    residual: bool, nota: bool):
    """Fused votes + routing, grid ``(iters + 1, n_blocks)``.

    Resident: pass 0 computes each votes block into the votes scratch and
    every later pass reads it back, so ``W`` is fetched once (its block
    index parks after pass 0).  Streamed: every pass recomputes the votes
    block from a re-streamed ``W`` tile."""
    lay = _LAYOUT[lanes]
    r_ref = refs[0] if residual else None
    o_ref = refs[1 if residual else 0]
    b_scr, s_scr, v_scr = refs[-4:-1] if resident else refs[-3:]
    p = pl.program_id(0)
    ib = pl.program_id(1)
    rows = _rows(ib, block_i)
    if resident:
        votes_scr = refs[-1]

        @pl.when(p == 0)
        def _():
            votes_scr[lay.votes_idx(rows)] = lay.votes(
                lay.u_block(u_ref, rows), w_ref[...])

        uh4 = votes_scr[lay.votes_idx(rows)]
    else:
        uh4 = lay.votes(lay.u_block(u_ref, rows), w_ref[...])
    _route_block(lay, p, ib, uh4, rows, b_scr, s_scr, v_scr, o_ref, r_ref,
                 n_passes=n_passes, n_blocks=n_blocks, nota=nota)


# ---------------------------------------------------------------------------
# Backward kernel: d u_hat stays in VMEM, like u_hat itself
# ---------------------------------------------------------------------------

def _softmax_bwd(lay, c, dc):
    """VJP of the class softmax given its OUTPUT c (a logits block).  The
    none-of-the-above softmax has the same VJP in terms of its output:
    the dropped column carries no cotangent."""
    return c * (dc - jnp.sum(c * dc, axis=lay.class_axis, keepdims=True))


def _squash_bwd(lay, s, dv):
    """VJP of the capsule squash at pre-activation s."""
    _, pull = jax.vjp(functools.partial(_squash_caps, lay), s)
    return pull(dv)[0]


def _routing_bwd_kernel(u_ref, w_ref, g_ref, du_ref, dw_ref, b2_scr, s2_scr,
                        acc_scr, v_scr, *votes, lanes: str, iters: int,
                        n_blocks: int, block_i: int, resident: bool,
                        nota: bool):
    """Grid ``(iters + 4, n_blocks)``.  Passes ``0..T`` replay the forward
    (the same fused s+b pass) over a ROLLING pair of logits slabs: the
    reference's ``stop_gradient(u_hat)`` convention means only ``b_{T-1}``
    / ``b_T`` are ever consumed again, so slot ``t % 2`` suffices.  Pass
    ``T+1`` seeds ``ds_T`` from the output cotangent (over ``s_T`` in the
    s pair, which nothing reads again; ``ds_{T-1}`` likewise replaces
    ``s_{T-1}``); pass ``T+2``
    rebuilds ``db_T`` block by block (so no logits-sized ``db`` slab is
    held), accumulates ``dv_{T-1} = sum_i u_hat . db_T`` and squash-vjps
    it into ``ds_{T-1}``; pass ``T+3`` emits du / dW per i-block from
    ``d u_hat = c_T (x) ds_T + c_{T-1} (x) ds_{T-1}``, never materialized
    beyond one i-block.  There is NO deep reverse recurrence: with the
    logits updates u_hat-constant, ``db_t`` for ``t < T`` feeds nothing.

    Resident rebuilds the votes once into scratch (pass 0) and reads them
    back, so ``W`` crosses HBM twice (rebuild + emit); streamed recomputes
    the votes block from a re-streamed ``W`` tile on every pass."""
    lay = _LAYOUT[lanes]
    t_total = iters
    p = pl.program_id(0)
    ib = pl.program_id(1)
    rows = _rows(ib, block_i)
    b_rows = lay.b_idx(rows)
    u_blk = lay.u_block(u_ref, rows)
    if resident:
        votes_scr = votes[0]

        @pl.when(p == 0)
        def _():
            votes_scr[lay.votes_idx(rows)] = lay.votes(u_blk, w_ref[...])

        uh4 = votes_scr[lay.votes_idx(rows)]
    else:
        uh4 = lay.votes(u_blk, w_ref[...])
    slot_last = t_total % 2
    slot_prev = (t_total - 1) % 2

    # ---- forward replay (passes 0 .. T) ----
    @pl.when((p == 0) & (ib == 0))
    def _():
        b2_scr[0] = jnp.zeros_like(b2_scr[0])

    @pl.when((p >= 1) & (p <= t_total))
    def _():  # iteration p's logits update rides this pass
        b2_scr[(p % 2,) + b_rows] = (b2_scr[((p - 1) % 2,) + b_rows]
                                     + lay.agreement(uh4, v_scr[...]))

    @pl.when(p <= t_total)
    def _():  # s-pass of iteration p (p == T is the final readout)
        @pl.when(ib == 0)
        def _():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        acc_scr[...] += lay.weighted_sum(
            _couplings(lay, b2_scr[(p % 2,) + b_rows], nota), uh4)

        @pl.when(ib == n_blocks - 1)
        def _():
            s2_scr[p % 2] = acc_scr[...]
            v_scr[...] = _squash_caps(lay, acc_scr[...])

    # ---- seed (T+1): ds_T from the cotangent, over s_T ----
    @pl.when((p == t_total + 1) & (ib == 0))
    def _():
        s2_scr[slot_last] = _squash_bwd(lay, s2_scr[slot_last],
                                        g_ref[...].astype(jnp.float32))

    # ---- one reverse pass (T+2): dv_{T-1} = sum_i u_hat . db_T ----
    @pl.when(p == t_total + 2)
    def _():
        @pl.when(ib == 0)
        def _():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        db = _softmax_bwd(
            lay, _couplings(lay, b2_scr[(slot_last,) + b_rows], nota),
            lay.agreement(uh4, s2_scr[slot_last]))
        acc_scr[...] += lay.weighted_sum(db, uh4)

        @pl.when(ib == n_blocks - 1)
        def _():  # ds_{T-1}, over s_{T-1}
            s2_scr[slot_prev] = _squash_bwd(lay, s2_scr[slot_prev],
                                            acc_scr[...])

    # ---- emit (T+3): d u_hat one i-block at a time -> du, dW ----
    @pl.when(p == t_total + 3)
    def _():
        c_last = _couplings(lay, b2_scr[(slot_last,) + b_rows], nota)
        c_prev = _couplings(lay, b2_scr[(slot_prev,) + b_rows], nota)
        duh = (lay.spread(c_last) * s2_scr[slot_last]
               + lay.spread(c_prev) * s2_scr[slot_prev])
        lay.emit(duh, w_ref, u_blk, du_ref, dw_ref)


# ---------------------------------------------------------------------------
# Forward dispatch + custom VJP
# ---------------------------------------------------------------------------

class _VRStatics(NamedTuple):
    """Hashable non-differentiable schedule for the fused custom_vjp."""

    iters: int
    num_classes: int
    mode: str
    block_i: int
    bwd_mode: str
    bwd_block_i: int
    interpret: bool
    lanes: str = "caps"
    bwd_lanes: str = "caps"
    nota: bool = False        # none-of-the-above category (``_couplings``)


def _vr_apply(st: _VRStatics, u, w, r=None):
    """Forward dispatch.  ``r [B, J*D]`` (optional) is a residual added to
    the routed output just before the store -- the ResCapsBlock coupling
    epilogue; it rides the kernel's output block, never a separate pass."""
    bsz, i_dim, c = u.shape
    jd = w.shape[1]
    j = st.num_classes
    d = jd // j
    lay = _LAYOUT[st.lanes]
    block_i = st.block_i
    n_blocks = pl.cdiv(i_dim, block_i)
    i_pad = n_blocks * block_i
    ut, wt = lay.to_kernel(u, w, j, i_pad)
    resident = st.mode == "resident"
    residual = r is not None
    n_passes = st.iters + 1
    vec_shape = lay.vec_shape(bsz, j, d)
    vec = pl.BlockSpec(vec_shape, lambda p, ib: (0, 0, 0, 0))
    if resident:      # W (and a streamed u) park after the votes pass
        def i_index(p, ib):
            return jnp.where(p == 0, ib, n_blocks - 1)
    else:             # re-streamed every pass
        def i_index(p, ib):
            return ib
    in_specs = [lay.u_spec(bsz, c, i_pad, block_i, i_index),
                lay.w_spec(c, j, d, block_i, i_index)]
    operands = [ut, wt]
    if residual:
        in_specs.append(vec)
        operands.append(lay.to_vec(r, j))
    scratch = [pltpu.VMEM(lay.logits_shape(bsz, j, i_pad), jnp.float32),
               pltpu.VMEM(vec_shape, jnp.float32),       # s accumulator
               pltpu.VMEM(vec_shape, jnp.float32)]       # squashed v
    if resident:
        scratch.append(pltpu.VMEM(lay.votes_shape(bsz, j, d, i_pad),
                                  jnp.float32))
    kernel = functools.partial(_routing_kernel, lanes=st.lanes,
                               n_passes=n_passes, n_blocks=n_blocks,
                               block_i=block_i, resident=resident,
                               residual=residual, nota=st.nota)
    out = pl.pallas_call(
        kernel,
        grid=(n_passes, n_blocks),
        in_specs=in_specs,
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct(vec_shape, u.dtype),
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=st.interpret,
    )(*operands)
    return lay.from_vec(out)


def _vr_grad(st: _VRStatics, u, w, g):
    """Backward dispatch: returns (du, dw) via the mode's Pallas kernel."""
    bsz, i_dim, c = u.shape
    jd = w.shape[1]
    j = st.num_classes
    d = jd // j
    lay = _LAYOUT[st.bwd_lanes]
    block_i = max(1, min(st.bwd_block_i, i_dim))
    n_blocks = pl.cdiv(i_dim, block_i)
    i_pad = n_blocks * block_i
    ut, wt = lay.to_kernel(u, w, j, i_pad)
    resident = st.bwd_mode == "resident"
    n_passes = st.iters + 4
    last = n_passes - 1
    if resident:      # W read by the votes rebuild and the emit pass only
        def i_index(p, ib):
            return jnp.where((p == 0) | (p == last), ib, n_blocks - 1)
    else:
        def i_index(p, ib):
            return ib

    # du/dW are written ONLY on the emit pass.  Pallas shuttles whatever
    # block the index map names through VMEM on every grid step, so each
    # output block is pinned to block 0 until the emit pass and crosses
    # HBM exactly once.
    def e_index(p, ib):
        return jnp.where(p == last, ib, 0)

    vec = lay.vec_shape(bsz, j, d)
    logits = lay.logits_shape(bsz, j, i_pad)
    scratch = [
        pltpu.VMEM((2,) + logits, jnp.float32),        # b: rolling pair
        pltpu.VMEM((2,) + vec, jnp.float32),           # s_{T-1}, s_T -> ds
        pltpu.VMEM(vec, jnp.float32),                  # s/dv accumulator
        pltpu.VMEM(vec, jnp.float32),                  # v
    ]
    if resident:
        scratch.append(pltpu.VMEM(lay.votes_shape(bsz, j, d, i_pad),
                                  jnp.float32))
    kernel = functools.partial(_routing_bwd_kernel, lanes=st.bwd_lanes,
                               iters=st.iters, n_blocks=n_blocks,
                               block_i=block_i, resident=resident,
                               nota=st.nota)
    du_shape, dw_shape = lay.grad_shapes(bsz, c, j, d, i_pad)
    du, dw = pl.pallas_call(
        kernel,
        grid=(n_passes, n_blocks),
        in_specs=[
            lay.u_spec(bsz, c, i_pad, block_i, i_index),
            lay.w_spec(c, j, d, block_i, i_index),
            pl.BlockSpec(vec, lambda p, ib: (0, 0, 0, 0)),
        ],
        out_specs=lay.grad_specs(bsz, c, j, d, block_i, e_index),
        out_shape=[jax.ShapeDtypeStruct(du_shape, u.dtype),
                   jax.ShapeDtypeStruct(dw_shape, w.dtype)],
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=st.interpret,
    )(ut, wt, lay.to_vec(g, j))
    return lay.from_grads(du, dw, i_dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _vr_core(st: _VRStatics, u, w):
    return _vr_apply(st, u, w)


def _vr_core_fwd(st: _VRStatics, u, w):
    return _vr_apply(st, u, w), (u, w)


def _vr_core_bwd(st: _VRStatics, res, g):
    u, w = res
    return _vr_grad(st, u, w, g)


_vr_core.defvjp(_vr_core_fwd, _vr_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _vr_core_res(st: _VRStatics, u, w, r):
    """Fused votes + routing + residual-add epilogue: ``r [B, J*D]`` is
    added to the routed output inside the kernel (one coupling half of a
    ResCapsBlock).  The add is linear, so the backward is exactly
    ``_vr_grad`` plus a pass-through cotangent for ``r``."""
    return _vr_apply(st, u, w, r)


def _vr_core_res_fwd(st: _VRStatics, u, w, r):
    return _vr_apply(st, u, w, r), (u, w)


def _vr_core_res_bwd(st: _VRStatics, res, g):
    u, w = res
    du, dw = _vr_grad(st, u, w, g)
    return du, dw, g


_vr_core_res.defvjp(_vr_core_res_fwd, _vr_core_res_bwd)


# ---------------------------------------------------------------------------
# Reversible residual capsule segment (MoCapsNet-style ResCapsBlocks)
# ---------------------------------------------------------------------------

def _res_segment_run(blocks, x, ws):
    """Forward walk of a run of additive-coupling blocks: for each block
    ``(i1, st_f, st_g)`` split the capsule axis at ``i1`` and apply
    ``y1 = x1 + F(x2)``, ``y2 = x2 + G(y1)`` -- each half one fused
    votes+routing kernel with the residual-add epilogue."""
    h = x
    for k, (i1, st_f, st_g) in enumerate(blocks):
        bsz = h.shape[0]
        x1, x2 = h[:, :i1], h[:, i1:]
        y1 = _vr_core_res(st_f, x2, ws[2 * k],
                          x1.reshape(bsz, -1)).reshape(x1.shape)
        y2 = _vr_core_res(st_g, y1, ws[2 * k + 1],
                          x2.reshape(bsz, -1)).reshape(x2.shape)
        h = jnp.concatenate([y1, y2], axis=1)
    return h


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _res_segment(blocks, x, ws):
    return _res_segment_run(blocks, x, ws)


def _res_segment_fwd(blocks, x, ws):
    # REVERSIBLE: only the segment OUTPUT and the weights are saved --
    # never x or any per-block intermediate -- so activation residency
    # stays flat no matter how many blocks the segment chains.
    y = _res_segment_run(blocks, x, ws)
    return y, (y, ws)


def _res_segment_bwd(blocks, res, g):
    """Invert the coupling block-by-block from the segment output.

    For each block (last first): recompute ``G(y1)`` / ``F(x2)`` forward
    (capturing their VJPs) to reconstruct ``x2 = y2 - G(y1)``, ``x1 = y1
    - F(x2)``, then push the cotangents through the coupling::

        d y1_total = g1 + dG/dy1^T g2
        d x1       = d y1_total
        d x2       = g2 + dF/dx2^T d y1_total

    Each half costs one forward + one backward kernel call -- the same
    recompute-from-(u, W) idiom as ``_vr_core_bwd``, lifted to block
    granularity."""
    y, ws = res
    dws = [None] * len(ws)
    for k in range(len(blocks) - 1, -1, -1):
        i1, st_f, st_g = blocks[k]
        wf, wg = ws[2 * k], ws[2 * k + 1]
        y1, y2 = y[:, :i1], y[:, i1:]
        g1, g2 = g[:, :i1], g[:, i1:]
        gy1, vjp_g = jax.vjp(
            lambda a, w: _vr_core(st_g, a, w).reshape(y2.shape), y1, wg)
        x2 = y2 - gy1
        fx2, vjp_f = jax.vjp(
            lambda a, w: _vr_core(st_f, a, w).reshape(y1.shape), x2, wf)
        x1 = y1 - fx2
        dy1_g, dwg = vjp_g(g2)
        g1_tot = g1 + dy1_g
        dx2_f, dwf = vjp_f(g1_tot)
        g = jnp.concatenate([g1_tot, g2 + dx2_f], axis=1)
        y = jnp.concatenate([x1, x2], axis=1)
        dws[2 * k], dws[2 * k + 1] = dwf, dwg
    return g, tuple(dws)


_res_segment.defvjp(_res_segment_fwd, _res_segment_bwd)


def _check_schedule(mode: str, bwd_mode: str, lanes: str,
                    bwd_lanes: str) -> None:
    if mode not in MODES or bwd_mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}/{bwd_mode!r}; "
                         f"choose from {MODES}")
    if lanes not in LAYOUTS or bwd_lanes not in LAYOUTS:
        raise ValueError(f"unknown lanes {lanes!r}/{bwd_lanes!r}; "
                         f"choose from {LAYOUTS}")


def _seg_statics(stat, i_dim: int, interpret: bool) -> _VRStatics:
    (iters, j, mode, block_i, bwd_mode, bwd_block_i, lanes, bwd_lanes,
     nota) = stat
    _check_schedule(mode, bwd_mode, lanes, bwd_lanes)
    return _VRStatics(iters=iters, num_classes=j, mode=mode,
                      block_i=max(1, min(block_i, i_dim)),
                      bwd_mode=bwd_mode,
                      bwd_block_i=max(1, min(bwd_block_i, i_dim)),
                      interpret=interpret, lanes=lanes, bwd_lanes=bwd_lanes,
                      nota=nota)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def res_caps_segment(x: jax.Array, ws, *, blocks,
                     interpret: bool) -> jax.Array:
    """x: [B, I, C] through a run of reversible ResCapsBlocks -> [B, I, C].

    ``blocks`` is a tuple of ``(i1, stats_f, stats_g)`` per block, where
    ``i1`` is the coupling split point and each ``stats`` is the half's
    ``(iters, num_out_caps, mode, block_i, bwd_mode, bwd_block_i, lanes,
    bwd_lanes, nota)`` schedule (from its plan op; see ``repro.kernels.ops``
    for the plan-aware wrapper).  ``ws`` are the flat per-half weights, F then G
    per block: ``wf [I-i1, i1*C, C]``, ``wg [i1, (I-i1)*C, C]``.

    Differentiable with NO saved activations: ``jax.grad`` reconstructs
    each block's input from its output (additive coupling is invertible)
    and replays the halves' fused backward kernels.
    """
    bsz, i_dim, c = x.shape
    if len(ws) != 2 * len(blocks):
        raise ValueError(f"res_caps_segment: {len(blocks)} blocks need "
                         f"{2 * len(blocks)} half-weights, got {len(ws)}")
    resolved = []
    for n, (i1, sf, sg) in enumerate(blocks):
        i2 = i_dim - i1
        if not 1 <= i1 < i_dim:
            raise ValueError(f"res_caps_segment: block {n} split i1={i1} "
                             f"outside [1, {i_dim - 1}]")
        wf, wg = ws[2 * n], ws[2 * n + 1]
        if wf.shape != (i2, i1 * c, c) or wg.shape != (i1, i2 * c, c):
            raise ValueError(
                f"res_caps_segment: block {n} weight shapes {wf.shape}/"
                f"{wg.shape} do not match the i1={i1} coupling of "
                f"[{bsz}, {i_dim}, {c}]")
        resolved.append((i1, _seg_statics(sf, i2, interpret),
                         _seg_statics(sg, i1, interpret)))
    return _res_segment(tuple(resolved), x, tuple(ws))


def _votes_routing(u: jax.Array, w: jax.Array, *, iters: int = 3,
                   num_classes: int = 10, mode: str = "resident",
                   block_i: int = 128, bwd_mode: str | None = None,
                   bwd_block_i: int | None = None, lanes: str = "caps",
                   bwd_lanes: str | None = None, nota: bool = False,
                   interpret: bool) -> jax.Array:
    """u: [B, I, C], w: [I, J*D, C] -> v: [B, J*D]; votes + full routing.

    ``mode``/``block_i``/``lanes`` come from the ExecutionPlan
    (``plan.op("ClassCaps-Routing")``); see ``repro.kernels.ops`` for the
    plan-driven wrapper.  The jnp routing (``capsnet.routing_by_agreement``)
    is the reference it is tested against.

    Differentiable: ``jax.grad`` runs the mode's backward Pallas kernel
    (``bwd_mode``/``bwd_block_i``/``bwd_lanes``, defaulting to the
    forward schedule --
    the plan chooses them independently because the backward's scratch is
    larger), recomputing the routing iterations from the saved ``(u, W)``
    residuals so neither ``u_hat`` nor its cotangent touches HBM.

    ``nota`` routes with the none-of-the-above category
    (``capsnet.couplings``), forward and backward.
    """
    bsz, i_dim, c = u.shape
    _, jd, _ = w.shape
    j = num_classes
    if jd % j:
        raise ValueError(f"votes dim {jd} not divisible by classes {j}")
    if iters < 1:
        raise ValueError(f"routing needs iters >= 1, got {iters}")
    bwd_mode = bwd_mode or mode
    bwd_lanes = bwd_lanes or lanes
    _check_schedule(mode, bwd_mode, lanes, bwd_lanes)
    st = _VRStatics(iters=iters, num_classes=num_classes, mode=mode,
                    block_i=max(1, min(block_i, i_dim)),
                    bwd_mode=bwd_mode,
                    bwd_block_i=max(1, min(bwd_block_i or block_i, i_dim)),
                    interpret=interpret, lanes=lanes, bwd_lanes=bwd_lanes,
                    nota=nota)
    return _vr_core(st, u, w)


_STATIC_ARGNAMES = ("iters", "num_classes", "mode", "block_i", "bwd_mode",
                    "bwd_block_i", "lanes", "bwd_lanes", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC_ARGNAMES)
def votes_routing(u: jax.Array, w: jax.Array, **schedule) -> jax.Array:
    """The fused votes + routing kernel with its custom VJP
    (``_votes_routing``: operands, ``schedule`` keywords and result),
    jitted.  A device trace names the call after this jit."""
    return _votes_routing(u, w, nota=False, **schedule)


@functools.partial(jax.jit, static_argnames=_STATIC_ARGNAMES)
def votes_routing_nota(u: jax.Array, w: jax.Array, **schedule) -> jax.Array:
    """``votes_routing`` with the none-of-the-above category, forward and
    backward, under a jit of its own name so that a trace tells it apart
    (``votes_routing_nota.N``; its backward ``transpose_jvp_jit_
    votes_routing_nota___.N``)."""
    return _votes_routing(u, w, nota=True, **schedule)
