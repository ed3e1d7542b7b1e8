"""What decides `correct`: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the engine served is drawn from the seed, always with the
one that served the most tokens in it: those it finished and, in a
closed loop whose answers outlast the window, those it was still serving
at the close, with the tokens they had by then. The reference (the module the
configuration names) runs once over each prompt followed by the tokens
the engine served, and at every served token reads how far that token's
logit lies below the reference's best logit at that position. The widest
such gap over the sample is compared with the cell's limit,
`limits/<workload>.json`, which also says how many requests to sample
and gives the readings the limit was set from.

Greedy decoding makes the served token the program's own best logit, so
the gap measures how far the program's logits stray from the
reference's, where it matters: at the token it chose.
"""
from __future__ import annotations

import functools
import importlib
import json
import math
from pathlib import Path

import numpy as np

from . import sut, weights

LIMITS_DIR = Path(__file__).resolve().parent / "limits"


def load_limits(workload: str) -> dict:
    return json.loads((LIMITS_DIR / f"{workload}.json").read_text())


def sequence_bucket(mix: dict) -> int:
    """Padded length of a reference row: the longest prompt plus the
    longest answer the mix can make, rounded up to 128."""
    pfx = mix.get("prefixes")
    prompt = (pfx["len"] + mix["suffix_len"]["max"] if pfx
              else mix["prompt_len"]["max"])
    n = prompt + mix["output_len"]["max"]
    return -(-n // 128) * 128


def sample(served: list, n: int, seed: int) -> list:
    """`n` requests (all, if fewer): the one that served the most tokens,
    then others drawn from the seed."""
    if not served:
        return []
    order = sorted(served, key=lambda tr: (-len(tr.tokens), tr.req.index))
    rest = order[1:]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [order[0]] + [rest[i] for i in sorted(pick)]


@functools.lru_cache(maxsize=None)
def _gap_fn(spec_json: str, window: int):
    spec = json.loads(spec_json)
    mod = importlib.import_module(
        f"{__package__}.reference.{spec['reference']}")
    return mod.gap_fn(spec, window)


def reference_gaps(spec: dict, seed: int, rows: list, bucket: int,
                   batch: int):
    """Per row (prompt, served): the reference's gap at each served token
    and its own best token there. Rows go in fixed batches of `batch`,
    padded, so one compiled program serves every run of the cell."""
    import jax.numpy as jnp
    fn = _gap_fn(json.dumps(spec, sort_keys=True),
                 sut.attention_window(spec))
    key = weights.root_key(seed)
    out = []
    for i in range(0, len(rows), batch):
        part = rows[i:i + batch]
        toks = np.zeros((batch, bucket), np.int32)
        tgts = np.full((batch, bucket), -1, np.int32)
        for j, (prompt, served) in enumerate(part):
            seq = list(prompt) + list(served[:-1])
            toks[j, :len(seq)] = seq
            p = len(prompt)
            tgts[j, p - 1:p - 1 + len(served)] = served
        gap, top = fn(key, jnp.asarray(toks), jnp.asarray(tgts))
        gap, top = np.asarray(gap), np.asarray(top)
        for j, (prompt, served) in enumerate(part):
            p = len(prompt)
            sl = slice(p - 1, p - 1 + len(served))
            out.append((gap[j, sl], top[j, sl]))
    return out


def compare(cell, seed: int, served: list, limits: dict) -> dict:
    """The verdict on one run over the requests that served tokens
    (finished, or cut at a closed loop's close): {"correct", "requests",
    "tokens", "argmax_agreement", "compared": {name: (value, limit)}}."""
    spec = cell.spec
    picked = sample(served, limits["sample_requests"], seed)
    bad_len = sum(len(tr.tokens) != tr.req.max_new_tokens
                  for tr in picked if not tr.cut)
    bad_tok = sum(not all(0 <= t < spec["vocab_size"] for t in tr.tokens)
                  for tr in picked)
    widest = math.inf
    agree = 0.0
    n_tok = sum(len(tr.tokens) for tr in picked)
    if picked and not bad_tok:
        res = reference_gaps(spec, seed,
                             [(tr.req.prompt, tr.tokens) for tr in picked],
                             sequence_bucket(cell.mix),
                             limits["sample_requests"])
        widest = float(max(g.max() for g, _ in res))
        agree = float(sum((t == np.asarray(tr.tokens)).sum()
                          for (_, t), tr in zip(res, picked)) / n_tok)
    compared = {
        "logit_gap_max": (widest, limits["logit_gap_max"]),
        "wrong_length_requests": (bad_len, 0),
        "out_of_vocab_requests": (bad_tok, 0),
    }
    ok = (len(picked) > 0 and bad_len == 0 and bad_tok == 0
          and widest <= limits["logit_gap_max"])
    return {"correct": ok, "requests": len(picked), "tokens": n_tok,
            "argmax_agreement": agree, "compared": compared}
