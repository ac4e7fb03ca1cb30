"""Operations and bytes of the CapsNet layers' mathematics, from a
configuration's sizes alone.

FLOPs count each multiply-add of a layer's contractions as two (the
squash, softmax and ReLU elementwise work is left out); bytes count the
layer's inputs, weights and outputs in float32, each moved once.  Nothing
here reads a plan, a kernel's grid or its number of passes over the
weights, so a change that fuses, re-tiles or streams differently is
judged against the same work.
"""

from __future__ import annotations

F32 = 4


def conv_sizes(ref, s: dict) -> dict:
    """Conv1 and PrimaryCaps as (out_hw, k, cin, cout)."""
    return {"conv1": (ref.conv1_out(s), s["conv1_kernel"], s["in_channels"],
                      s["conv1_channels"]),
            "pc": (ref.pc_out(s), s["pc_kernel"], s["conv1_channels"],
                   s["num_primary_groups"] * s["primary_dim"])}


def conv_flops(b: int, out_hw: int, k: int, cin: int, cout: int) -> float:
    return 2.0 * b * out_hw * out_hw * k * k * cin * cout


def routing_flops(b: int, lay: dict) -> float:
    """Votes (I x J x D x C multiply-adds per image), then per routing
    iteration the weighted sum and the agreement (I x J x D each), and
    the final weighted sum."""
    i, j, d, c = (lay["in_caps"], lay["num_caps"], lay["caps_dim"],
                  lay["in_dim"])
    return 2.0 * b * i * j * d * (c + 2 * lay["iters"] + 1)


def decoder_flops(s: dict, b: int) -> float:
    h1, h2 = s["decoder_hidden"]
    d_in = s["num_classes"] * s["class_dim"]
    d_out = s["image_hw"] ** 2 * s["in_channels"]
    return 2.0 * b * (d_in * h1 + h1 * h2 + h2 * d_out)


def serve_flops(ref, s: dict, b: int) -> float:
    """Forward to the class capsules' lengths (serving needs no
    reconstruction)."""
    cs = conv_sizes(ref, s)
    return (conv_flops(b, *cs["conv1"]) + conv_flops(b, *cs["pc"])
            + sum(routing_flops(b, lay) for lay in ref.routing_stack(s)))


def train_flops(ref, s: dict, b: int) -> float:
    """Forward with the decoder, and a backward of twice the forward's
    work for every layer but Conv1, whose input needs no gradient.
    Recomputation (the reversible blocks', a replayed producer) is not
    counted."""
    cs = conv_sizes(ref, s)
    fwd = serve_flops(ref, s, b) + decoder_flops(s, b)
    return 3.0 * fwd - conv_flops(b, *cs["conv1"])
