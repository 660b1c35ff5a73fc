"""Least work of one decode step of a decoder, from the shapes the
configuration's `decoder` block states, whatever implements it: every
sequence of the batch meets every weight once and attends over its cache
slots, and the chip reads every weight and the whole cache once.

A StarCoder2 layer (arXiv:2402.19173): q, k, v and o projections with
biases, two LayerNorms with a bias, a two-matrix MLP with biases; the
embedding is tied to the LM head, and a final LayerNorm comes before it.
"""

from __future__ import annotations


def layer_params(dec: dict) -> int:
    """Parameters of one decoder layer."""
    d, h, kv, hd, ff = (dec["d_model"], dec["n_heads"], dec["n_kv_heads"],
                        dec["head_dim"], dec["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d        # q, k, v, o
    attn += h * hd + 2 * kv * hd + d                         # their biases
    mlp = 2 * d * ff + ff + d
    norms = 2 * 2 * d
    return attn + mlp + norms


def params(dec: dict) -> tuple[int, int]:
    """(non-embedding parameters, embedding parameters) of the decoder."""
    body = dec["n_layers"] * layer_params(dec) + 2 * dec["d_model"]
    return body, dec["vocab_size"] * dec["d_model"]


def cache_slots(dec: dict, max_seq: int) -> int:
    """Cache slots a decode step attends over once the cache is full."""
    return min(dec["window"], max_seq) if dec.get("window") else max_seq


def decode_work(dec: dict, batch: int, slots: int,
                weight_bytes: int = 2, cache_bytes: int = 2
                ) -> tuple[float, float]:
    """(ops, bytes) of one decode step of `batch` sequences whose caches
    hold `slots` positions: a multiply and an add per weight and sequence
    (the LM head's d x V included), and per query head q.k and p.v over
    the slots; every weight and the whole cache (k and v) read once."""
    body, embed = params(dec)
    ops = 2.0 * body * batch + 2.0 * dec["d_model"] * dec["vocab_size"] \
        * batch
    ops += 4.0 * dec["n_layers"] * dec["n_heads"] * dec["head_dim"] \
        * slots * batch
    nbytes = (body + embed) * weight_bytes
    nbytes += 2.0 * dec["n_layers"] * batch * slots * dec["n_kv_heads"] \
        * dec["head_dim"] * cache_bytes
    return ops, float(nbytes)
