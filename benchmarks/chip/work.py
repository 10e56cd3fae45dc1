"""Operations and bytes that the work needs, counted from a configuration's
published shapes.

A roofline share has to count the same work whatever implements it, so
nothing here asks the program what it did: the counts follow from the
configuration file (the published ``config.json`` keys) and from the
lengths the traffic served.  Matrix products count two operations per
multiply-add.  Bytes count what a decode round has to read at the
configuration's serving precision (bfloat16): the weights once per
round, and the keys and values of the live positions of each active
lane, read and written.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

BF16 = 2


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    gated: bool
    bias: bool
    norm_affine: bool
    tied: bool


def dims(conf: dict) -> Dims:
    """Shapes of a configuration file (Hugging Face ``config.json`` keys)."""
    d = conf["hidden_size"]
    heads = conf["num_attention_heads"]
    return Dims(
        vocab=conf["vocab_size"],
        d=d,
        layers=conf["num_hidden_layers"],
        heads=heads,
        kv_heads=conf.get("num_key_value_heads", heads),
        head_dim=conf.get("head_dim") or d // heads,
        ff=conf["intermediate_size"],
        gated=conf["hidden_act"] == "silu",
        bias=bool(conf.get("use_bias", conf.get("attention_bias", False))),
        norm_affine=conf.get("norm_type") == "layer_norm",
        tied=bool(conf.get("tie_word_embeddings", False)),
    )


def layer_matrix_params(m: Dims) -> int:
    """Weights of one layer's matrix products (attention and MLP)."""
    attn = m.d * m.head_dim * (2 * m.heads + 2 * m.kv_heads)
    mlp = (3 if m.gated else 2) * m.d * m.ff
    return attn + mlp


def layer_vector_params(m: Dims) -> int:
    """Biases and norm scales of one layer."""
    n = 0
    if m.bias:
        n += m.head_dim * (m.heads + 2 * m.kv_heads) + m.d + m.ff + m.d
    if m.norm_affine:
        n += 4 * m.d
    return n


def param_count(m: Dims) -> int:
    emb = m.vocab * m.d * (1 if m.tied else 2)
    final = 2 * m.d if m.norm_affine else 0
    return emb + m.layers * (layer_matrix_params(m) + layer_vector_params(m)) + final


def kv_bytes_per_token(m: Dims) -> int:
    """Keys and values of one position, over all layers, in bfloat16."""
    return m.layers * 2 * m.kv_heads * m.head_dim * BF16


def attention_flops(m: Dims, keys: int) -> int:
    """Scores and weighted sum of one query against ``keys`` positions."""
    return m.layers * 4 * m.heads * m.head_dim * keys


def prefill_flops(m: Dims, n: int) -> int:
    """A prompt of ``n`` tokens, causal, with logits at its last position."""
    linear = 2 * m.layers * layer_matrix_params(m) * n
    attn = m.layers * 4 * m.heads * m.head_dim * (n * (n + 1) // 2)
    return linear + attn + 2 * m.d * m.vocab


def decode_flops(m: Dims, positions: Iterable[int]) -> int:
    """One decode round: each active lane's token at its ``position``
    (the index its key and value are written at) attends to
    ``position + 1`` keys and produces a full row of logits."""
    per_lane = 2 * (m.layers * layer_matrix_params(m) + m.d * m.vocab)
    total = 0
    for p in positions:
        total += per_lane + attention_flops(m, p + 1)
    return total


def decode_weight_bytes(m: Dims) -> int:
    """Weights one decode round reads, in bfloat16: every layer and the
    output head (the embedding rows a round reads are negligible)."""
    return BF16 * (
        m.layers * (layer_matrix_params(m) + layer_vector_params(m))
        + m.d * m.vocab
        + (2 * m.d if m.norm_affine else 0)
    )


def decode_bytes(m: Dims, positions: Iterable[int]) -> int:
    """Bytes one decode round needs: the weights once, and per active
    lane the keys and values of ``position + 1`` positions read and one
    written."""
    kv = kv_bytes_per_token(m)
    total = decode_weight_bytes(m)
    for p in positions:
        total += kv * (p + 2)
    return total
