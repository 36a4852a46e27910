"""The work latent attention and held experts require, from shapes alone.

The counterpart of ``work.py`` for configurations whose reference is
``glm4moe_lite``: ``d`` is that reference's dims dict (``L`` layers of
which ``dense`` lead with a SwiGLU of ``F``; ``D``; ``H`` heads; the
``q_lora``/``kv_lora`` ranks; ``dn``/``dr``/``dv`` head parts; ``E``
routed experts of ``Fe`` of which ``held`` live here, ``k`` per token; a
shared expert of ``Fs``; ``V``).  As in ``work.py`` these count what the
algorithm needs: causal attention at its half over real prompt tokens,
one LM-head row per prefill, decode over each live slot's own length,
and held experts at their expected share, ``k * held / E`` expert rows
per token.
"""
from __future__ import annotations

from work import BF16, causal_pairs

F32 = 4


def _qk(d: dict) -> int:
    return d["dn"] + d["dr"]


def attn_params(d: dict) -> int:
    """Projection weights one token multiplies through in one layer."""
    H = d["H"]
    return (d["D"] * d["q_lora"] + d["q_lora"] * H * _qk(d)
            + d["D"] * (d["kv_lora"] + d["dr"])
            + d["kv_lora"] * H * (d["dn"] + d["dv"]) + H * d["dv"] * d["D"])


def ffn_params(d: dict) -> float:
    """MLP weights one token multiplies through, over the whole stack:
    the dense layers' SwiGLU; per MoE layer the router, the held experts
    at their expected share and the shared expert."""
    moe = (d["D"] * d["E"] + d["k"] * d["held"] / d["E"] * 3 * d["D"] * d["Fe"]
           + 3 * d["D"] * d["Fs"])
    return d["dense"] * 3 * d["D"] * d["F"] + (d["L"] - d["dense"]) * moe


def prefill_attention_flops(d: dict, n: int) -> int:
    """Causal multi-head attention over ``n`` positions at head dims
    ``dn + dr`` (scores) and ``dv`` (values), every layer."""
    return d["L"] * 2 * d["H"] * (_qk(d) + d["dv"]) * causal_pairs(n)


def prefill_flops(d: dict, n: int) -> float:
    """A prefill of ``n`` real prompt tokens: the projections without
    absorption, attention, the MLPs, and one LM-head row."""
    return (2 * (d["L"] * attn_params(d) + ffn_params(d)) * n
            + prefill_attention_flops(d, n) + 2 * d["D"] * d["V"])


def decode_attention_flops(d: dict, length: int) -> int:
    """One token's absorbed latent attention over ``length`` cached
    positions, every layer: scores over latent and rope keys, P . latent,
    and the absorptions q_nope W_kb and o W_vb."""
    C, H = d["kv_lora"], d["H"]
    return d["L"] * 2 * H * ((C + d["dr"]) * length + C * length
                             + d["dn"] * C + C * d["dv"])


def decode_flops(d: dict, length: int) -> float:
    """One decoded token whose cache holds ``length`` positions (itself
    included)."""
    return (2 * (d["L"] * attn_params(d) + ffn_params(d))
            + decode_attention_flops(d, length) + 2 * d["D"] * d["V"])


def served_flops(d: dict, steps) -> float:
    """Engine steps as the serving client records them (``serving.py``)."""
    return (sum(prefill_flops(d, n) for s in steps for n in s["prefill"])
            + sum(decode_flops(d, n) for s in steps for n in s["decode"]))


# ---------------------------------------------------------------------------
# Kernels.  Per call over all layers; FLOPs and HBM bytes.
# ---------------------------------------------------------------------------
def mla_decode(d: dict, positions: int, slots: int) -> tuple[int, int]:
    """The latent decode kernel over ``slots`` live slots that read
    ``positions`` cached positions in all: the bf16 latent and rope key of
    each, every layer; each slot's bf16 queries (latent and rope parts)
    and float32 output."""
    C, R, H = d["kv_lora"], d["dr"], d["H"]
    flops = d["L"] * 2 * H * (2 * C + R) * positions
    bytes_ = d["L"] * (positions * (C + R) * BF16
                       + slots * H * ((C + R) * BF16 + C * F32))
    return flops, bytes_


def mla_prefill(d: dict, n: int) -> tuple[int, int]:
    """Causal flash forward of one prefill over ``n`` real positions:
    reads q, k, v and writes o, each ``H`` heads wide, in bf16."""
    flops = prefill_attention_flops(d, n)
    bytes_ = d["L"] * n * d["H"] * (2 * _qk(d) + 2 * d["dv"]) * BF16
    return flops, bytes_
