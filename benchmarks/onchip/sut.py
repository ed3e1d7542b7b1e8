"""The system under test: the serving engine of `src/repro`, built for one
configuration file with weights the harness made.

Everything the harness imports from the program goes through here.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from . import weights as W

ROOT = Path(__file__).resolve().parents[2]
SRC = ROOT / "src"


def import_program():
    """Put the program's `src/` on the path; FileNotFoundError without it."""
    if not (SRC / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout "
                                f"({SRC / 'repro'} is missing)")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def model_config(spec: dict):
    """The program's ModelConfig for a configuration file."""
    import_program()
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=spec["name"], family="dense",
        n_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        head_dim=W.dims(spec)["head_dim"], d_ff=spec["intermediate_size"],
        vocab=spec["vocab_size"], act=spec["hidden_act"], norm="rmsnorm",
        rope=True, rope_theta=spec["rope_theta"],
        max_seq=spec["max_position_embeddings"],
        tie_embeddings=spec["tie_word_embeddings"])


def policy(spec: dict, backend: str | None = None, name: str | None = None):
    import_program()
    from repro.launch.train import policy_from_name
    eng = spec["engine"]
    return policy_from_name(name or eng["policy"]).with_backend(
        backend or eng["backend"])


def build_params(spec: dict, cfg, pol, seed: int):
    """The program's serving weights, made on the device in one jitted
    call: each layer's bfloat16 weights from the seed, quantized by the
    program's own quantize-once pass, one layer at a time (`lax.map`), so
    the float tree never exists whole."""
    import_program()
    from repro.launch.serve import prepare_serving_params
    pad = cfg.padded_vocab - spec["vocab_size"]

    def make(key):
        def one(index):
            lw = W.layer(spec, key, index)
            block = {"attn_norm": {"w": lw["attn_norm"]},
                     "attn": {n: lw[n] for n in ("wq", "wk", "wv", "wo")},
                     "mlp_norm": {"w": lw["mlp_norm"]},
                     "mlp": {n: lw[n] for n in ("w1", "w2", "w3")}}
            return prepare_serving_params(block, pol)
        tree = {"embed": jnp.pad(W.embed(spec, key), ((0, pad), (0, 0))),
                "blocks": jax.lax.map(
                    one, jnp.arange(spec["num_hidden_layers"])),
                "final_norm": {"w": W.final_norm(spec, key)}}
        if not spec["tie_word_embeddings"]:
            tree["lm_head"] = prepare_serving_params(
                {"lm_head": jnp.pad(W.head(spec, key),
                                    ((0, 0), (0, pad)))}, pol)["lm_head"]
        return tree

    params = jax.jit(make)(W.root_key(seed))
    _check_tree(cfg, pol, params)
    return params


def _check_tree(cfg, pol, params):
    """The harness's tree must be the one the program builds itself."""
    from repro.launch.serve import prepare_serving_params
    from repro.models import model as M
    want = jax.eval_shape(lambda k: prepare_serving_params(
        M.init_params(cfg, k), pol), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
            != [(a.shape, a.dtype) for a in jax.tree.leaves(got)]):
        raise ValueError("harness weights do not match the program's "
                         "parameter tree")


def build_engine(spec: dict, cfg, pol, params, seed: int):
    import_program()
    from repro.serving import ServingEngine
    eng = spec["engine"]
    return ServingEngine(
        cfg, params, policy=pol, max_slots=eng["slots"],
        max_len=eng["max_len"], prefill_chunk=eng["prefill_chunk"],
        seed=seed & 0x7FFFFFFF, kv_block_size=eng["kv_block_size"],
        kv_blocks=eng["kv_blocks"], prefix_cache=eng["prefix_cache"],
        overlap=eng["overlap"])


def request(prompt, max_new_tokens: int):
    """A greedy request with no end-of-sequence token."""
    import_program()
    from repro.serving.api import Request
    return Request(prompt=prompt, max_new_tokens=max_new_tokens,
                   eos_id=None)


def attention_window(spec: dict) -> int:
    """Positions in one row of the paged KV view: the engine allocates
    max_len plus one prefill chunk, in whole blocks."""
    eng = spec["engine"]
    bs = eng["kv_block_size"]
    alloc = eng["max_len"] + eng["prefill_chunk"]
    return -(-alloc // bs) * bs
