"""Traffic from a mix file and a seed: the one generator every mix uses.

A mix file (`traffic/<name>.json`) holds only parameters:

- "loop": "closed" (one client per engine slot, each sending its next
  request when the last one finished) or "open" (arrivals on a schedule,
  whatever the engine does);
- "rate_per_s" (open loop): arrivals in the window are those of a
  Poisson process of that rate, conditioned on their count: that many
  arrival times drawn uniformly over the window;
- "prompt_len", "output_len", and for shared prefixes "suffix_len":
  {"median", "sigma", "min", "max"}, a lognormal clipped to [min, max];
- "prefixes" (optional): {"count", "len", "zipf_s"}: each request starts
  with one of `count` prefixes of `len` tokens, chosen with Zipf weights
  k^-zipf_s, then its own suffix;
- "first_output": "residual" (closed loop): a client's first request has
  the remaining length of a request already under way, so the batch
  starts staggered;
- "shape_seed": the seed of the lengths, prefix choices and arrival
  times. Every `--seed` gets the same ones, so the seed changes which
  tokens are sent, never how much work a run holds or when: an open
  loop's whole schedule is fixed (the order of arrivals alone moved a
  cell's TTFT p90 by a fifth from seed to seed), and a closed loop's
  clients take the same requests in the seed's order;
- "base" (optional): another mix whose keys this one extends.

The program receives only the generated token lists.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Req:
    """One generated request."""
    index: int
    prompt: List[int]
    max_new_tokens: int
    due_s: Optional[float] = None     # open loop: arrival after window start
    client: Optional[int] = None      # closed loop: the client sending it
    prefix: Optional[int] = None      # which shared prefix it starts with


@dataclasses.dataclass
class Traffic:
    mix: dict
    prefixes: List[List[int]]          # shared prefixes, in index order
    requests: List[Req]                # open: by due time; closed: per client

    def client_queue(self, client: int) -> List[Req]:
        return [r for r in self.requests if r.client == client]


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    mix = json.loads((directory / f"{name}.json").read_text())
    if "base" in mix:
        mix = {**load_mix(mix["base"], directory),
               **{k: v for k, v in mix.items() if k != "base"}}
    return mix


def lengths(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    mu = math.log(dist["median"])
    x = np.exp(rng.normal(mu, dist["sigma"], n))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def zipf_choices(rng: np.random.Generator, count: int, s: float,
                 n: int) -> np.ndarray:
    w = np.arange(1, count + 1, dtype=np.float64) ** -s
    return rng.choice(count, size=n, p=w / w.sum())


def residual(rng: np.random.Generator, out: np.ndarray) -> np.ndarray:
    """Remaining lengths of requests caught mid-flight: a request is caught
    with probability proportional to its length, at a uniform point."""
    caught = rng.choice(out, size=len(out), p=out / out.sum())
    return np.maximum(1, np.ceil(caught * rng.random(len(out)))).astype(
        np.int64)


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             clients: int, per_client: int = 48) -> Traffic:
    """Requests for one run of `seconds` at `seed`. `clients` is the
    closed loop's client count; `per_client` its requests per client."""
    shape = np.random.default_rng(mix["shape_seed"])
    rng = np.random.default_rng(seed)
    pfx = mix.get("prefixes")
    prefixes = ([rng.integers(0, vocab, pfx["len"]).tolist()
                 for _ in range(pfx["count"])] if pfx else [])

    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        due = np.sort(shape.random(n) * seconds)
    elif mix["loop"] == "closed":
        n = clients * per_client
        due = None
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")

    out = lengths(shape, mix["output_len"], n)
    body = lengths(shape, mix["suffix_len" if pfx else "prompt_len"], n)
    which = (zipf_choices(shape, pfx["count"], pfx["zipf_s"], n)
             if pfx else np.full(n, -1))
    if due is None:
        order = rng.permutation(n)
        out, body, which = out[order], body[order], which[order]
    client = [None] * n if due is not None else [i % clients
                                                  for i in range(n)]
    if mix["loop"] == "closed" and mix.get("first_output") == "residual":
        # each client's first request is one already under way
        first_out = residual(shape, lengths(shape, mix["output_len"], n))
        first_body = lengths(shape, mix["prompt_len"], clients)
        pick = rng.permutation(clients)
        out = np.concatenate([first_out[:clients][pick], out])
        body = np.concatenate([first_body[pick], body])
        which = np.concatenate([np.full(clients, -1), which])
        client = list(range(clients)) + client
        n += clients

    reqs = []
    for i in range(n):
        tokens = rng.integers(0, vocab, int(body[i])).tolist()
        prompt = (prefixes[which[i]] + tokens) if pfx else tokens
        reqs.append(Req(i, prompt, int(out[i]),
                        due_s=None if due is None else float(due[i]),
                        client=client[i],
                        prefix=None if not pfx else int(which[i])))
    return Traffic(mix, prefixes, reqs)
