"""Operation and byte counts from shapes: the yardstick's side of every
utilization and roofline share.

Counts are of the work the algorithm needs, whatever implements it: a
multiply-add is 2 FLOPs, an attention query costs 4 * D FLOPs per key it
can see (Q.K and P.V), and a kernel's bytes are its operands read once and
its result written once.  Padded lanes, masked keys, recomputation and the
integer work of a softmax datapath count for nothing, so a program that
does such work shows a lower share, which is the signal.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``; a device
    that is not listed is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; have {sorted(table['devices'])}")


# --- attention ------------------------------------------------------------

def causal_pairs(seq: int) -> int:
    """(query, visible key) pairs of one causal sequence of ``seq`` tokens."""
    return seq * (seq + 1) // 2


def chunk_pairs(start: int, n: int) -> int:
    """Pairs of ``n`` queries at positions ``start .. start + n - 1`` that
    each see every key up to and including their own position."""
    return n * start + n * (n + 1) // 2


def attention_flops(heads: int, d_head: int, pairs: int) -> float:
    """Forward attention: Q.K and P.V, 2 FLOPs per multiply-add each."""
    return 4.0 * heads * d_head * pairs


def attention_bwd_flops(heads: int, d_head: int, pairs: int) -> float:
    """Backward attention without recomputing the scores: dP = dO.V,
    dV = P.dO, dQ = dS.K and dK = dS.Q."""
    return 8.0 * heads * d_head * pairs


def kv_bytes_per_token(kv_heads: int, d_head: int, cache_dtype: str) -> float:
    """Bytes of K plus V for one position of one layer, read once: int8
    raws plus one float32 scale per (head, position) for fp2fx8."""
    if cache_dtype == "fp2fx8":
        return 2.0 * kv_heads * (d_head + 4)
    return 2.0 * kv_heads * d_head * _itemsize(cache_dtype)


def attention_bytes(heads: int, kv_heads: int, d_head: int, queries: int,
                    keys: int, act_dtype: str, cache_dtype: str) -> float:
    """Q read and O written for ``queries`` rows, K and V read for
    ``keys`` positions, each once."""
    qo = 2.0 * queries * heads * d_head * _itemsize(act_dtype)
    return qo + keys * kv_bytes_per_token(kv_heads, d_head, cache_dtype)


def _itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[dtype]


# --- whole model ------------------------------------------------------------

def layer_matmul_params(m: dict) -> int:
    """Weights of one block that take part in a matrix product."""
    d, h, kv, dh, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_head"], m["d_ff"])
    attn = d * h * dh * 2 + d * kv * dh * 2
    mlp = d * ff * (3 if m["mlp_gated"] else 2)
    return attn + mlp


def matmul_params(m: dict) -> int:
    """Every matrix-product weight of the model: the blocks and the
    unembedding (the embedding lookup is a gather)."""
    return m["n_layers"] * layer_matmul_params(m) + m["vocab"] * m["d_model"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward FLOPs per token of a causal LM step at
    ``seq``: 6 per matrix-product weight, and 12 * H * D per visible key
    in every layer.  Recomputation is not counted."""
    dense = 6.0 * matmul_params(m)
    attn = 3.0 * m["n_layers"] * attention_flops(
        m["n_heads"], m["d_head"], causal_pairs(seq)) / seq
    return dense + attn


def prefill_flops(m: dict, prompt_lens, logits_rows: int) -> float:
    """Forward FLOPs of the real prompt tokens of ``prompt_lens``: the
    blocks for every token, causal attention over each prompt, and the
    unembedding for the ``logits_rows`` positions whose logits are used."""
    layer = 2.0 * m["n_layers"] * layer_matmul_params(m)
    toks = sum(prompt_lens)
    pairs = sum(causal_pairs(n) for n in prompt_lens)
    attn = m["n_layers"] * attention_flops(m["n_heads"], m["d_head"], pairs)
    return layer * toks + attn + 2.0 * m["vocab"] * m["d_model"] * logits_rows


def decode_flops(m: dict, positions) -> float:
    """Forward FLOPs of decoding one token at each of ``positions``: the
    blocks, the unembedding, and attention over the ``p + 1`` positions a
    token at ``p`` can see."""
    per_tok = 2.0 * matmul_params(m)
    attn = m["n_layers"] * attention_flops(m["n_heads"], m["d_head"],
                                           sum(p + 1 for p in positions))
    return per_tok * len(positions) + attn


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float | None:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the measured ``seconds``, in %; None when nothing
    was measured."""
    if seconds <= 0 or flops <= 0:
        return None
    bound = max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * bound / seconds
