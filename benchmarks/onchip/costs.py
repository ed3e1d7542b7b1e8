"""Operations and bytes of the served model's work, from shapes alone.

The yardstick of the roofline and mfu metrics: what the algorithm needs,
each operand read once and each result written once, whatever the
kernels actually move. An operation is a multiply or an add.
"""
from __future__ import annotations

from . import weights as W


def layer_params(spec: dict) -> int:
    """Matmul weights of one block (elements)."""
    d = W.dims(spec)
    return sum(d[fin] * d[fout] for _, fin, fout in W.LAYER_MATMULS)


def padded_vocab(spec: dict) -> int:
    """Head rows the program computes: the vocabulary padded to 256."""
    return -(-spec["vocab_size"] // 256) * 256


def gemm(m: int, k: int, n: int, out_bytes: int = 4) -> tuple:
    """One int8 GEMM with its dequant epilogue: (ops, bytes). Bytes are
    the int8 activation and weight codes, the f32 per-channel scale, and
    the result (float32 from the kernel)."""
    return 2.0 * m * k * n, float(m * k + k * n + 4 * n + out_bytes * m * n)


def step_gemms(spec: dict, rows: int, head_rows: int) -> list:
    """(m, k, n) of every GEMM in one model step over `rows` tokens, the
    head over `head_rows` of them: per block the q, k, v, o, gate, up and
    down projections, then the head (over the padded vocabulary)."""
    d = W.dims(spec)
    calls = [(rows, d[fin], d[fout]) for _, fin, fout in W.LAYER_MATMULS]
    calls = calls * spec["num_hidden_layers"]
    return calls + [(head_rows, spec["hidden_size"], padded_vocab(spec))]


def gemm_step(spec: dict, rows: int, head_rows: int) -> tuple:
    """Summed (ops, bytes) of `step_gemms`."""
    ops = nbytes = 0.0
    for m, k, n in step_gemms(spec, rows, head_rows):
        o, b = gemm(m, k, n)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def attention_decode(spec: dict, contexts) -> tuple:
    """One layer's paged decode attention over rows whose caches hold
    `contexts` positions (the new token's included): (ops, bytes). Scores
    and weighted values are 2 * hd operations per head and position each;
    bytes are the int8 K and V codes with one f32 scale per position and
    head, and the bf16 query and output."""
    d = W.dims(spec)
    hd, h, kvh = (d["head_dim"], spec["num_attention_heads"],
                  spec["num_key_value_heads"])
    ctx = float(sum(contexts))
    ops = 4.0 * h * hd * ctx
    nbytes = ctx * kvh * 2 * (hd + 4) + len(contexts) * h * hd * 2 * 2
    return ops, nbytes


def token_ops(spec: dict, position: int, head: bool = True) -> float:
    """Model operations to compute one token at 0-based `position`: every
    matmul weight twice (multiply and add), attention over the
    position + 1 keys it sees (scores and weighted values), and the head
    when the token's logits are computed."""
    d = W.dims(spec)
    ops = 2.0 * layer_params(spec) * spec["num_hidden_layers"]
    ops += (4.0 * spec["num_attention_heads"] * d["head_dim"]
            * (position + 1) * spec["num_hidden_layers"])
    if head:
        ops += 2.0 * spec["hidden_size"] * spec["vocab_size"]
    return ops
