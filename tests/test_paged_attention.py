"""Fused paged decode attention (kernels/paged_attention): bit-exactness
of the block-table-walking op vs the historical gather+masked-attention
composition on both backends, across {fp, int8-KV dequant, fully-integer}
x {exact, CORDIC softmax} x ragged lengths (0-length idle rows, shared
block tables), plus engine-level token equality for every cache family
with the fused path active on the decode hot loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import PrecisionPolicy
from repro.core.fxp import FORMATS, dequantize, quantize
from repro.kernels import dispatch
from repro.models import layers as L
from repro.models import model as M
from repro.serving import Request, ServingEngine

KEY = jax.random.PRNGKey(0)
BACKENDS = ("reference", "pallas-interpret")
FAMILIES = ("qwen2_5_14b", "mamba2_370m", "zamba2_1p2b", "deepseek_moe_16b")


# ---------------------------------------------------------------------------
# op level: fused kernel vs the gather+masked composition
# ---------------------------------------------------------------------------

def _layout(name, nb):
    """(tables [B, MB], query positions [B], decoded tokens [B]) of one
    table layout; the sentinel nb marks unallocated slots.

    ragged: row 1 spans the whole table, rows 0 and 2 SHARE their blocks
        (prefix sharing), row 3 is idle (all sentinel); tail slots of
        active rows are unallocated.
    wide: 16 slots a row with 1-3 live (the served cells' live share).
    boundary: valid lengths that end exactly on a block boundary (rows 0
        and 2) and mid-block (rows 1 and 3).
    allocated_tail: rows holding ALLOCATED blocks past their valid
        length, which every position masks (the CORDIC exp still weighs
        them by about 1.7e-38).
    """
    if name == "ragged":
        mb, rows = 4, [[3, 1], [0, 2, 5, 7], [3, 1], []]
        lengths, n_valid = [6, 14, 6, 0], [1, 1, 1, 0]
    elif name == "wide":
        mb, rows = 16, [[4], [8, 0], [2, 6, 1], [5, 3]]
        lengths, n_valid = [2, 7, 9, 5], [1, 1, 1, 1]
    elif name == "boundary":
        mb, rows = 6, [[1, 2], [3, 4, 5], [6, 7, 8, 0], [2]]
        lengths, n_valid = [7, 9, 15, 2], [1, 1, 1, 1]
    else:
        mb, rows = 8, [[0, 1, 2, 3, 4, 5], [6, 7, 8], [8, 3], [1, 7, 0]]
        lengths, n_valid = [5, 3, 4, 0], [1, 1, 0, 1]
    tables = np.full((len(rows), mb), nb, np.int32)
    for r, blocks in enumerate(rows):
        tables[r, :len(blocks)] = blocks
    return (tables, np.asarray(lengths, np.int32),
            np.asarray(n_valid, np.int32))


LAYOUTS = ("ragged", "wide", "boundary", "allocated_tail")


def _pools(quant, layout="ragged", seed=0):
    """Random pools (9 blocks of 4 positions) + one of the LAYOUTS."""
    rng = np.random.default_rng(seed)
    b, kvh, g, hd = 4, 2, 3, 8
    nb, bs = 9, 4
    q = jnp.asarray(rng.normal(size=(b, 1, kvh * g, hd)).astype(np.float32))
    kf = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)).astype(np.float32))
    vf = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)).astype(np.float32))
    tables, lengths, n_valid = _layout(layout, nb)
    tables = jnp.asarray(tables)
    lengths = jnp.asarray(lengths)                    # query positions
    kv_valid = lengths + jnp.asarray(n_valid)
    positions = lengths[:, None]
    if quant:
        fmt = FORMATS["fxp8"]
        kc, ks = quantize(kf, fmt, axis=3)
        vc, vs = quantize(vf, fmt, axis=3)
        return q, kc, vc, ks, vs, tables, lengths, kv_valid, positions, fmt
    return (q, kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16), None, None,
            tables, lengths, kv_valid, positions, None)


def _gather_path(q, kc, vc, ks, vs, tables, lengths, kv_valid, positions,
                 fmt, int_attention, policy):
    """The pre-fused layers composition: materialise the contiguous view,
    then masked attention over it — the numerics contract the fused op
    must reproduce bit-for-bit."""
    if fmt is not None and int_attention:
        return L.int8_decode_attention(
            q, L.gather_block_kv(kc, tables), L.gather_block_kv(vc, tables),
            L.gather_block_kv(ks, tables), L.gather_block_kv(vs, tables),
            fmt, policy, positions=positions, kv_valid_len=kv_valid)
    if fmt is not None:
        k_full = dequantize(L.gather_block_kv(kc, tables),
                            L.gather_block_kv(ks, tables), jnp.bfloat16)
        v_full = dequantize(L.gather_block_kv(vc, tables),
                            L.gather_block_kv(vs, tables), jnp.bfloat16)
    else:
        k_full, v_full = (L.gather_block_kv(kc, tables),
                          L.gather_block_kv(vc, tables))
    return L.chunked_attention(q, k_full, v_full, causal=True,
                               q_offset=lengths, policy=policy,
                               kv_valid_len=kv_valid)


CASES = [
    ("fp-exact", False, False, None),
    ("fp-cordic", False, False, "cordic"),
    ("int8kv-exact", True, False, "exact"),
    ("int8kv-cordic", True, False, "cordic"),
    ("intattn-exact", True, True, "exact"),
    ("intattn-cordic", True, True, "cordic"),
]


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name,quant,int_attn,impl", CASES,
                         ids=[c[0] for c in CASES])
def test_fused_op_bit_exact_vs_gather_path(backend, name, quant, int_attn,
                                           impl, layout):
    del name
    (q, kc, vc, ks, vs, tables, lengths, kv_valid, positions,
     fmt) = _pools(quant, layout)
    policy = (None if impl is None else
              PrecisionPolicy.flexpe(8, af_impl=impl,
                                     backend=backend))
    got = dispatch.paged_attention(
        q, kc, vc, ks, vs, tables, policy, backend, lengths=lengths,
        kv_valid=kv_valid, positions=positions, fmt=fmt,
        int_attention=int_attn)
    ref = _gather_path(q, kc, vc, ks, vs, tables, lengths, kv_valid,
                       positions, fmt, int_attn, policy)
    # bit-exact everywhere, idle (0-length) row included: both paths see
    # the same zero-filled unallocated positions by construction, and a
    # masked allocated block's weight lies below one ulp of the row
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(ref, np.float32))
    assert np.isfinite(np.asarray(got, np.float32)).all()


def test_cordic_exp_weighs_masked_scores():
    """The trap the allocated_tail layout guards: under the CORDIC exp a
    masked score (-1e30) still weighs its value, by about 1.7e-38, so the
    walk skipping an allocated block past the valid length is exact only
    because that weight is below one ulp of the row."""
    from repro.kernels.paged_attention.ref import _exp_fn
    pol = PrecisionPolicy.flexpe(8, af_impl="cordic")
    w = float(_exp_fn(pol)(jnp.float32(-1e30)))
    assert 0.0 < w < 1e-37
    assert float(_exp_fn(PrecisionPolicy.flexpe(8, af_impl="exact"))(
        jnp.float32(-1e30))) == 0.0


def test_fused_op_shared_tables_rows_agree():
    """Rows pointing at the same physical blocks with the same length
    (prefix sharing / CoW parents) must produce identical outputs."""
    (q, kc, vc, ks, vs, tables, lengths, kv_valid, positions,
     fmt) = _pools(True)
    q = q.at[2].set(q[0])            # same query too
    out = dispatch.paged_attention(
        q, kc, vc, ks, vs, tables, None, "pallas-interpret",
        lengths=lengths, kv_valid=kv_valid, positions=positions, fmt=fmt)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[2]))


# ---------------------------------------------------------------------------
# engine level: the fused path serves decode for every cache family
# ---------------------------------------------------------------------------

def _params(cfg):
    return M.init_params(cfg, KEY, dtype=jnp.float32)


def _prompt(i, plen, cfg, shared=0):
    sys_p = jax.random.PRNGKey(2)
    tail_k = jax.random.fold_in(jax.random.PRNGKey(1), i)
    if cfg.input_mode == "tokens":
        head = jax.random.randint(sys_p, (shared,), 0, cfg.vocab)
        tail = jax.random.randint(tail_k, (plen,), 0, cfg.vocab)
    else:
        head = jax.random.normal(sys_p, (shared, cfg.d_model), jnp.bfloat16)
        tail = jax.random.normal(tail_k, (plen, cfg.d_model), jnp.bfloat16)
    return jnp.concatenate([head, tail]) if shared else tail


def _req(i, plen, cfg, gen=5, shared=0):
    return Request(prompt=_prompt(i, plen, cfg, shared=shared),
                   max_new_tokens=gen, id=i)


def _run(cfg, p, reqs, policy=None, **kw):
    eng = ServingEngine(cfg, p, policy=policy, max_slots=2, max_len=24,
                        prefill_chunk=4, **kw)
    return {f.id: f.tokens for f in eng.run(reqs)}


@pytest.mark.parametrize("arch", FAMILIES)
def test_engine_fused_paged_matches_contiguous(arch):
    """Greedy decode with the fused paged-attention hot loop is
    token-identical to the contiguous engine for every cache family."""
    cfg = get_config(arch).reduced()
    p = _params(cfg)
    reqs = lambda: [_req(i, pl, cfg) for i, pl in
                    [(0, 5), (1, 11), (2, 8)]]
    assert _run(cfg, p, reqs()) == _run(cfg, p, reqs(), kv_block_size=4)


@pytest.mark.parametrize("int_attn", [False, True],
                         ids=["dequant", "int-attention"])
def test_engine_fused_paged_int8_kv_pallas_interpret(int_attn):
    """int8-KV policies on the pallas-interpret backend: the fused kernel
    (and its int+cordic reference fallback) keep token equality with the
    contiguous layout."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    pol = PrecisionPolicy.flexpe(8, backend="pallas-interpret")
    if int_attn:
        import dataclasses
        pol = dataclasses.replace(pol, int_attention=True)
    reqs = lambda: [_req(0, 9, cfg), _req(1, 4, cfg)]
    assert (_run(cfg, p, reqs(), policy=pol)
            == _run(cfg, p, reqs(), policy=pol, kv_block_size=4))


def test_engine_fused_paged_prefix_cached():
    """Shared/CoW block tables (prefix cache hits) feed the fused kernel
    the same physical blocks from several rows; tokens must still match
    the cold contiguous run."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    reqs = lambda: [_req(i, 3, cfg, shared=8) for i in range(3)]
    cold = _run(cfg, p, reqs())
    warm = _run(cfg, p, reqs(), kv_block_size=4, prefix_cache=True)
    assert cold == warm


def test_shard_local_tables_rebase():
    """shard_local_tables maps a GLOBAL block table onto one pool shard's
    LOCAL ids: owned entries rebase to [0, blocks_per_shard), everything
    else (other shards' blocks AND the global sentinel) becomes the
    LOCAL sentinel — and running the fused kernel per shard over a
    single-shard-resident row reproduces the full-pool walk."""
    from repro.kernels.paged_attention.ops import shard_local_tables
    nb, bps = 8, 4                       # 2 shards of 4 blocks
    tables = jnp.asarray([[0, 5, 3, nb],
                          [4, 7, nb, nb]], jnp.int32)
    t0 = np.asarray(shard_local_tables(tables, 0, bps, nb))
    t1 = np.asarray(shard_local_tables(tables, 1, bps, nb))
    np.testing.assert_array_equal(t0, [[0, bps, 3, bps],
                                       [bps, bps, bps, bps]])
    np.testing.assert_array_equal(t1, [[bps, 1, bps, bps],
                                       [0, 3, bps, bps]])
    # a row resident entirely on shard 1: the shard-local kernel run over
    # the shard's pool slice equals the global run over the whole pool
    rng = np.random.default_rng(3)
    kvh, g, hd, bs = 2, 3, 8, 4
    q = jnp.asarray(rng.normal(size=(1, 1, kvh * g, hd)).astype(np.float32))
    kf = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)).astype(np.float32))
    vf = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)).astype(np.float32))
    row = jnp.asarray([[4, 5, 6, nb]], jnp.int32)     # blocks on shard 1
    lens = jnp.asarray([10], jnp.int32)
    kvv, pos = lens + 1, lens[:, None]
    pol = PrecisionPolicy.bf16()
    full = dispatch.paged_attention(
        q, kf, vf, None, None, row, pol, backend="reference",
        lengths=lens, kv_valid=kvv, positions=pos)
    local = dispatch.paged_attention(
        q, kf[4:8], vf[4:8], None, None,
        shard_local_tables(row, 1, bps, nb), pol, backend="reference",
        lengths=lens, kv_valid=kvv, positions=pos)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(local))
