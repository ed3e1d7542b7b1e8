"""Random bfloat16 weights of a llama-family configuration, from a seed.

The harness makes the weights, not the program: the plain reference and
the system under test read the same values, and neither takes anything
the other made. Every leaf is a function of (seed, layer, leaf name)
alone, so the reference can build one layer at a time and the program's
stacked tree holds the very same values.

Values come from raw random bits by exact float arithmetic (a 24-bit
integer to float32, times constants, rounded once to bfloat16), so any
program that builds a leaf gets the same bits, whatever XLA fuses.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
HEAD_STD = 0.02

#: (leaf name, fan-in key, fan-out key) of one block's matmul weights
LAYER_MATMULS = (
    ("wq", "hidden_size", "q_width"),
    ("wk", "hidden_size", "kv_width"),
    ("wv", "hidden_size", "kv_width"),
    ("wo", "q_width", "hidden_size"),
    ("w1", "hidden_size", "intermediate_size"),
    ("w3", "hidden_size", "intermediate_size"),
    ("w2", "intermediate_size", "hidden_size"),
)
_LEAF_IDS = {name: i for i, name in enumerate(
    [m[0] for m in LAYER_MATMULS] + ["attn_norm", "mlp_norm"])}


def dims(spec: dict) -> dict:
    """The sizes a layer needs, derived from a configuration file."""
    hd = spec.get("head_dim") or spec["hidden_size"] // spec[
        "num_attention_heads"]
    return {"hidden_size": spec["hidden_size"],
            "intermediate_size": spec["intermediate_size"],
            "q_width": spec["num_attention_heads"] * hd,
            "kv_width": spec["num_key_value_heads"] * hd,
            "head_dim": hd}


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one over 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def uniform(key, shape, std: float, center: float = 0.0) -> jax.Array:
    """bfloat16 values, uniform with standard deviation `std`."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    half = jnp.float32(math.sqrt(3.0) * std)
    return ((u * 2.0 - 1.0) * half + jnp.float32(center)).astype(
        jnp.bfloat16)


def layer(spec: dict, key, index) -> dict:
    """One block's weights: {name: bfloat16 array}; `index` may be traced."""
    d = dims(spec)
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    out = {}
    for name, fin, fout in LAYER_MATMULS:
        out[name] = uniform(jax.random.fold_in(lk, _LEAF_IDS[name]),
                            (d[fin], d[fout]), 1.0 / math.sqrt(d[fin]))
    for name in ("attn_norm", "mlp_norm"):
        out[name] = uniform(jax.random.fold_in(lk, _LEAF_IDS[name]),
                            (d["hidden_size"],), 0.25 / math.sqrt(3.0), 1.0)
    return out


def embed(spec: dict, key) -> jax.Array:
    """[vocab_size, hidden_size] token embedding."""
    return uniform(jax.random.fold_in(key, 0),
                   (spec["vocab_size"], spec["hidden_size"]), EMBED_STD)


def final_norm(spec: dict, key) -> jax.Array:
    return uniform(jax.random.fold_in(key, 2), (spec["hidden_size"],),
                   0.25 / math.sqrt(3.0), 1.0)


def head(spec: dict, key) -> jax.Array:
    """[hidden_size, vocab_size] output head (the embedding's transpose
    where the configuration ties them)."""
    if spec["tie_word_embeddings"]:
        return embed(spec, key).T
    return uniform(jax.random.fold_in(key, 3),
                   (spec["hidden_size"], spec["vocab_size"]), HEAD_STD)
