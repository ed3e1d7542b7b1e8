"""Plain reference of a llama-family decoder served under Flex-PE FxP.

What the configuration states, written out in plain `jax.numpy` over one
whole sequence at a time, with no cache, no batching across requests, no
kernels and nothing of the program under test:

- embedding, then per block: RMSNorm, attention with RoPE (halves
  rotated), RMSNorm, SwiGLU MLP, each branch added to the residual;
  final RMSNorm and the output head;
- every GEMM on integer codes: weights rounded half to even onto
  8-bit codes with a scale per output channel (per tensor for a
  tied head), activations onto codes with a scale per token, products
  summed exactly in int32 and dequantized by both scales;
- SiLU as x * sigmoid(x) on the dequantized sum, the sigmoid by CORDIC
  (range-extended hyperbolic exp, then linear-vectoring division), its
  output snapped to the code grid per token;
- attention scores in float32 at HIGHEST precision against K/V held as
  int8 codes with a scale per position and head; softmax as the CORDIC
  exp of each score minus its row maximum, summed, and the weighted V
  divided by that sum in linear-vectoring CORDIC;
- bfloat16 wherever the configuration stores activations.

The departures from the program are those of a reference: a scale per
token where the program scales a whole batch, exact causal masking, and
logits left in float32.

`gap_fn` returns, for each position whose next token was served, how far
that token's logit lies below the best logit there.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .. import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
_LN2 = math.log(2.0)
#: FxP8: 8-bit codes; CORDIC stages of the hyperbolic exp and of the
#: linear division at the 8-bit Pareto point
BITS, HR_STAGES, LV_STAGES = 8, 4, 5


# -- CORDIC -------------------------------------------------------------------

def _hr_iterations(stages: int) -> list[int]:
    """Hyperbolic iteration indices 1, 2, ...; 4, 13 and 40 are done twice
    (the classic repeat that makes the hyperbolic rotation converge)."""
    idx, i = [], 1
    while len(idx) < stages:
        idx.append(i)
        if i in (4, 13, 40) and len(idx) < stages:
            idx.append(i)
        i += 1
    return idx


def cordic_exp(z, stages: int):
    """e^z: z = k ln2 + r, 2^k exactly, e^r = cosh r + sinh r by
    hyperbolic-rotation CORDIC."""
    z = jnp.clip(z, -87.0, 88.0)
    k = jnp.floor(z * (1.0 / _LN2) + 0.5)
    r = z - k * _LN2
    its = _hr_iterations(stages)
    gain = 1.0
    for i in its:
        gain *= math.sqrt(1.0 - 2.0 ** (-2 * i))
    x = jnp.full_like(r, 1.0 / gain)
    y = jnp.zeros_like(r)
    for i in its:
        d = jnp.where(r >= 0, 1.0, -1.0)
        x, y = x + d * y * (2.0 ** -i), y + d * x * (2.0 ** -i)
        r = r - d * math.atanh(2.0 ** -i)
    ki = jnp.clip(k, -126.0, 127.0).astype(jnp.int32)
    two_k = jax.lax.bitcast_convert_type((ki + 127) << 23, jnp.float32)
    return (x + y) * two_k


def cordic_divide(num, den, stages: int):
    """num / den for |num| <= |den| by linear-vectoring CORDIC."""
    x, y = den, num
    q = jnp.zeros_like(num)
    for i in range(1, stages + 1):
        d = -jnp.sign(x * y)
        d = jnp.where(d == 0, 1.0, d)
        y = y + d * x * (2.0 ** -i)
        q = q - d * (2.0 ** -i)
    return q


def cordic_silu(x, hr: int, lv: int):
    e = cordic_exp(-jnp.abs(x), hr)
    num = jnp.where(x >= 0, jnp.ones_like(e), e)
    return x * cordic_divide(num, 1.0 + e, lv)


# -- fixed point --------------------------------------------------------------

def to_codes(x, bits: int, axis):
    """Round half to even onto signed `bits`-bit codes; scale = amax/qmax
    over `axis` (None: the whole tensor). Returns (codes, scale)."""
    qmax = 2 ** (bits - 1) - 1
    x = x.astype(jnp.float32)
    amax = (jnp.max(jnp.abs(x)) if axis is None
            else jnp.max(jnp.abs(x), axis=axis, keepdims=True))
    scale = jnp.maximum(amax, 1e-12) / qmax
    codes = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
    return codes.astype(jnp.int8), scale


def snap(x, bits: int, axis=-1):
    codes, scale = to_codes(x, bits, axis)
    return codes.astype(jnp.float32) * scale


def gemm(x, w):
    """x [..., K] (activation codes per token) @ w (codes, scale) ->
    float32 [..., N]."""
    wc, ws = w
    xc, xs = to_codes(x, BITS, -1)
    acc = jax.lax.dot_general(xc, wc, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (xs * ws)


# -- blocks -------------------------------------------------------------------

def rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(jnp.bfloat16) * w


def rope(x, theta):
    """x [S, heads, hd], positions 0..S-1."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(jnp.bfloat16)


def attention(q, k, v, hr: int, lv: int):
    """One sequence, causal. q [S, H, hd]; k, v [S, KV, hd] -> [S, H, hd]."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, h // kvh, hd).astype(jnp.float32)
    sc = jnp.einsum("qkgd,pkd->kgqp", qg, k.astype(jnp.float32),
                    precision=HIGHEST) * (1.0 / math.sqrt(hd))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jnp.where(causal, cordic_exp(sc - sc.max(-1, keepdims=True), hr),
                  0.0)
    den = p.sum(-1)[..., None]
    o = jnp.einsum("kgqp,pkd->kgqd", p, v.astype(jnp.float32),
                   precision=HIGHEST)
    norm = jnp.maximum(jnp.max(jnp.abs(o), -1, keepdims=True), den) + 1e-9
    o = cordic_divide(o / norm, den / norm, lv)
    return o.transpose(2, 0, 1, 3).reshape(s, h, hd).astype(jnp.bfloat16)


def kv_roundtrip(x):
    """K or V through the int8 cache: codes per (position, head)."""
    codes, scale = to_codes(x, BITS, -1)
    return (codes.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def softmax_lv_stages(window: int) -> int:
    return max(5, min(int(math.ceil(math.log2(max(window, 2)))) + 6, 24))


def gap_fn(spec: dict, window: int):
    """Jitted (key, tokens [B, S] int32, targets [B, S] int32) ->
    (gap, top) [B, S]: at each position t whose target (the token served
    after t) is >= 0, the best logit minus the target's, and the
    reference's own best token. `window` is the attention row the
    configuration's softmax divides over (its LV stage count)."""
    return jax.jit(functools.partial(_gaps, spec, window))


def _quantized_layer(spec, key, index):
    lw = W.layer(spec, key, index)
    out = {n: to_codes(lw[n], BITS, -2) for n, _, _ in W.LAYER_MATMULS}
    out["attn_norm"], out["mlp_norm"] = lw["attn_norm"], lw["mlp_norm"]
    return out


def _gaps(spec, window, key, tokens, targets):
    d = W.dims(spec)
    hd, nh, kvh = (d["head_dim"], spec["num_attention_heads"],
                   spec["num_key_value_heads"])
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    hr, lv = HR_STAGES, LV_STAGES
    lv_attn = softmax_lv_stages(window)
    b, s = tokens.shape
    x = W.embed(spec, key)[tokens]                          # [B, S, D] bf16

    def block(x, index):
        lw = _quantized_layer(spec, key, index)

        def attend(xs):
            hn = rmsnorm(xs, lw["attn_norm"], eps)
            q = gemm(hn, lw["wq"]).astype(jnp.bfloat16)
            k = gemm(hn, lw["wk"]).astype(jnp.bfloat16)
            v = gemm(hn, lw["wv"]).astype(jnp.bfloat16)
            q = rope(q.reshape(s, nh, hd), theta)
            k = kv_roundtrip(rope(k.reshape(s, kvh, hd), theta))
            v = kv_roundtrip(v.reshape(s, kvh, hd))
            o = attention(q, k, v, hr, lv_attn).reshape(s, nh * hd)
            return gemm(o, lw["wo"]).astype(jnp.bfloat16)

        x = x + jax.lax.map(attend, x)
        hn = rmsnorm(x, lw["mlp_norm"], eps)
        gate = snap(cordic_silu(gemm(hn, lw["w1"]), hr, lv), BITS)
        up = gemm(hn, lw["w3"]).astype(jnp.bfloat16)
        y = gemm(gate.astype(jnp.bfloat16) * up, lw["w2"])
        return x + y.astype(jnp.bfloat16), None

    x, _ = jax.lax.scan(block, x, jnp.arange(spec["num_hidden_layers"]))
    xn = rmsnorm(x, W.final_norm(spec, key), eps)
    hw = W.head(spec, key)
    head = to_codes(hw, BITS, None if spec["tie_word_embeddings"] else -2)

    def score(args):
        xs, tgt = args
        logits = gemm(xs, head)                       # [S, V] f32
        best = logits.max(-1)
        tl = jnp.take_along_axis(logits, jnp.maximum(tgt, 0)[:, None],
                                 axis=1)[:, 0]
        gap = jnp.where(tgt >= 0, best - tl, 0.0)
        return gap, jnp.argmax(logits, -1).astype(jnp.int32)

    return jax.lax.map(score, (xn, targets))
