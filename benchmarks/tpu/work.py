"""The work each operation requires, from shapes alone.

These count what the algorithm needs, never what the current kernels
happen to execute: causal attention at its half, real prompt tokens and
not padded buckets, one LM-head row per prefill, the int8 K/V bytes of
each live slot's length, and no recomputation.  A padded bucket, a wasted
head row or a recompute then shows as a lower share of the peak.

``d`` is the dims dict of a configuration's reference (``L``, ``D``,
``H``, ``Hkv``, ``hd``, ``F``, ``V``).
"""
from __future__ import annotations

BF16 = 2


def block_matmul_params(d: dict) -> int:
    """Weights one token multiplies through in the layer stack."""
    per_layer = (d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["Hkv"] * d["hd"]
                 + d["H"] * d["hd"] * d["D"] + 3 * d["D"] * d["F"])
    return d["L"] * per_layer


def causal_pairs(n: int) -> int:
    """Query-key pairs of causal attention over ``n`` positions."""
    return n * (n + 1) // 2


def attention_flops(d: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` query-key pairs, every layer and head."""
    return d["L"] * 4 * d["H"] * d["hd"] * pairs


def prefill_flops(d: dict, n: int) -> int:
    """A prefill of ``n`` real prompt tokens and its one LM-head row."""
    return (2 * block_matmul_params(d) * n + attention_flops(d, causal_pairs(n))
            + 2 * d["D"] * d["V"])


def decode_flops(d: dict, length: int) -> int:
    """One decoded token whose cache holds ``length`` positions (itself
    included)."""
    return (2 * block_matmul_params(d) + attention_flops(d, length)
            + 2 * d["D"] * d["V"])


def served_flops(d: dict, steps) -> int:
    """Engine steps as the serving client records them: each prefill over
    its real prompt tokens, each decoded token over its cache length."""
    return (sum(prefill_flops(d, n) for s in steps for n in s["prefill"])
            + sum(decode_flops(d, n) for s in steps for n in s["decode"]))


def train_flops(d: dict, batch: int, seq: int) -> int:
    """Forward and backward of one step: three times the forward."""
    fwd = batch * (2 * block_matmul_params(d) * seq + 2 * d["D"] * d["V"] * seq
                   + attention_flops(d, causal_pairs(seq)))
    return 3 * fwd


# ---------------------------------------------------------------------------
# Kernels.  Per call over all layers; FLOPs and HBM bytes.
# ---------------------------------------------------------------------------
def flash_fwd(d: dict, batch: int, n: int) -> tuple[int, int]:
    """Causal flash forward over ``n`` real positions: reads Q, K, V and
    writes O in bf16."""
    flops = batch * attention_flops(d, causal_pairs(n))
    bytes_ = batch * d["L"] * n * d["hd"] * (2 * d["H"] + 2 * d["Hkv"]) * BF16
    return flops, bytes_


def flash_train(d: dict, batch: int, n: int) -> tuple[int, int]:
    """Forward plus backward (dV, dP, dQ, dK): six matmuls over the causal
    pairs.  Bytes: the forward's, then Q, K, V, O, dO read and dQ, dK, dV
    written."""
    f_flops, f_bytes = flash_fwd(d, batch, n)
    b_bytes = batch * d["L"] * n * d["hd"] * (
        3 * d["H"] + 2 * d["Hkv"] + d["H"] + 2 * d["Hkv"]) * BF16
    return 3 * f_flops, f_bytes + b_bytes


def kvq_decode(d: dict, lengths) -> tuple[int, int]:
    """One decode round's int8 attention over live slots of ``lengths``
    cached positions: int8 K and V plus one f32 scale per position and
    head for each, and each slot's bf16 query and output."""
    total = sum(int(n) for n in lengths)
    flops = d["L"] * 4 * d["H"] * d["hd"] * total
    kv = d["L"] * 2 * d["Hkv"] * total * (d["hd"] + 4)
    qo = d["L"] * len(lengths) * 2 * d["H"] * d["hd"] * BF16
    return flops, kv + qo


def roofline_seconds(flops: int, bytes_: int, peaks: dict) -> float:
    """The least time the chip could take: compute or memory, the larger."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def mfu_percent(flops: int, record: dict):
    """Required ``flops`` of the traced stretch over its length times the
    chip's bf16 peak; None when nothing was traced."""
    if not flops:
        return None
    return 100.0 * flops / (record["trace"]["window_s"]
                            * record["peaks"]["bf16_flops_per_s"])
