"""ModelExecutor: the device-owning half of the serving engine.

Owns the jit-compiled prefill/decode steps, the slot-pool cache, the
coalesced host mirrors of the device control arrays (`lengths`,
`block_tables`, SSM reset rows), and — the piece that makes the
overlapped loop possible — a **device-resident sampled-token feedback
buffer**: decode and sampling are fused into one jitted step that writes
each slot's sampled token straight back into the `[max_slots]` buffer
the next decode tick reads its inputs from. The host therefore never
has to sync a sampled token to build the next dispatch; it drains token
values one tick behind, purely to emit events and detect EOS.

Invalid rows (`n_valid == 0`) are fed token 0 / a zero embed inside the
fused step — bit-identical to the host-built decode blocks the
pre-split engine uploaded every tick, which matters for MoE capacity
routing (cross-row cumsum) and keeps batch-composition independence
intact.

The compiled step triple is cached across executor instances keyed on
everything that shapes the computation, so spinning up a new engine
against the same (cfg, policy, pool geometry) costs no recompile.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..launch import steps as S
from ..launch.mesh import make_tp_mesh
from ..models import model as M

#: compiled (prefill, decode+sample, seed) step triples shared across
#: executor instances, plus ("verify", key, chunk)-keyed chunked verify
#: steps for speculative decoding
_STEP_CACHE: dict = {}

#: Every serving step keeps the casts it is written with. XLA on a TPU
#: otherwise drops a bf16 cast whose result is cast straight back to f32
#: (excess precision), where the CPU and the Pallas kernels round: the
#: reference attention's bf16-dequantized K/V stayed f32 on a v5e while
#: the fused kernel's were rounded, about 1% apart per layer.
_jit = functools.partial(
    jax.jit, compiler_options={"xla_allow_excess_precision": False})


def _sample_core(vocab: int, logits, keys, temps, topks):
    """logits [R, V*] -> tokens [R]: per-row greedy / temperature / top-k.
    Pure row-wise math (argmax / sort / per-key categorical), so a row's
    token is independent of what other rows share the call — the property
    that lets prefill-seeded rows and decode rows sample in separate
    dispatches while staying bit-identical to a single batched sample."""
    lg = logits[:, :vocab].astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1)
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    kidx = jnp.clip(topks - 1, 0, vocab - 1)
    thresh = jnp.take_along_axis(srt, kidx[:, None], axis=1)
    filt = jnp.where((topks[:, None] > 0) & (lg < thresh), -jnp.inf, lg)
    scaled = filt / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


def step_cache_key(cfg, policy, mesh, max_slots, alloc, chunk, params,
                   kv_block_size=None, kv_blocks=None):
    """The `_STEP_CACHE` key: everything that shapes the compiled triple.

    Tier-relevant property (exposed as `ModelExecutor.step_cache_key`):
    the policy — hence the serving TIER — is part of the key, while
    param VALUES are not (only the treedef), so same-tier replicas of a
    heterogeneous fleet share one compilation and different-tier
    replicas get their own specialization, exactly the paper's
    "run-time precision switching = selection among compiled modes"."""
    return (cfg, policy, mesh, max_slots, alloc, chunk,
            jax.tree_util.tree_structure(params), kv_block_size, kv_blocks)


def _compiled_steps(cfg, policy, mesh, max_slots, alloc, chunk, params,
                    kv_block_size=None, kv_blocks=None):
    """Jit the (prefill, decode+sample, seed) triple with full input/output
    sharding trees resolved against the REAL param tree (serving TP
    preset): QuantizedTensor codes + scales and the embedding table split
    over `model`, everything else float replicates, and the paged pool
    partitions its block axis. On a 1-device mesh every sharding collapses
    to trivially-replicated and this is exactly the old unsharded jit."""
    key = step_cache_key(cfg, policy, mesh, max_slots, alloc, chunk, params,
                         kv_block_size, kv_blocks)
    if key not in _STEP_CACHE:
        pspec = jax.eval_shape(lambda: params)
        prefill_fn, p_shard, _, pf_in, pf_out = S.build_prefill_step(
            cfg, mesh, policy, with_cache=True, batch=max_slots,
            max_len=alloc, chunk=chunk, kv_block_size=kv_block_size,
            kv_blocks=kv_blocks, params_spec=pspec)
        decode_fn, _, _, dc_in, dc_out = S.build_serve_step(
            cfg, mesh, policy, batch=max_slots, max_len=alloc, chunk=1,
            kv_block_size=kv_block_size, kv_blocks=kv_blocks,
            params_spec=pspec)
        c_shard = dc_in[1]
        rep = NamedSharding(mesh, P())
        vocab, d_model = cfg.vocab, cfg.d_model
        tokens_mode = cfg.input_mode == "tokens"

        def decode_sample(params, cache, token_buf, n_valid, keys, temps,
                          topks):
            """Fused decode + sample + feedback: the [B] token buffer is
            both this tick's decode input and (for valid rows) the
            landing spot of this tick's sampled tokens."""
            live = n_valid > 0
            if tokens_mode:
                tokens = jnp.where(live, token_buf, 0)[:, None]
            else:
                # embeds-mode stubs feed the one-hot of the sampled token
                oh = jax.nn.one_hot(token_buf % d_model, d_model,
                                    dtype=jnp.bfloat16)
                tokens = (oh * live[:, None])[:, None, :]
            logits, new_cache = decode_fn(params, cache, tokens, n_valid)
            toks = _sample_core(vocab, logits, keys, temps, topks)
            new_buf = jnp.where(live, toks, token_buf)
            return toks, new_buf, new_cache

        def seed(token_buf, rows, logits, keys, temps, topks):
            """Sample rows that just finished prefill and scatter their
            first tokens into the feedback buffer (device-side — the
            host never round-trips the values)."""
            toks = _sample_core(vocab, logits, keys, temps, topks)
            return toks, token_buf.at[rows].set(toks)

        # decode_sample wraps decode_fn, so its sharding trees extend the
        # serve step's: token buffer / sampling knobs replicate, sampled
        # tokens come back replicated (every shard holds the full vocab
        # logits — seeding the next tick needs no cross-shard traffic)
        _STEP_CACHE[key] = (
            _jit(prefill_fn, donate_argnums=(1,),
                    in_shardings=pf_in, out_shardings=pf_out),
            _jit(decode_sample, donate_argnums=(1, 2),
                    in_shardings=(p_shard, c_shard, rep, rep, rep, rep, rep),
                    out_shardings=(rep, rep, dc_out[1])),
            _jit(seed, donate_argnums=(0,),
                    in_shardings=(rep,) * 6, out_shardings=(rep, rep)),
            p_shard, c_shard)
    return _STEP_CACHE[key]


def build_verify_step(cfg, mesh, policy, batch, max_len, chunk,
                      kv_block_size=None, kv_blocks=None, params_spec=None):
    """The speculative-verify step: an explicit token grid [B, chunk] +
    per-row `n_valid` against the slot-pool cache, scoring EVERY position
    in one chunked dispatch — `decode_step(..., last_only=False)`, the
    same ragged machinery chunked prefill runs on — and reducing each
    position to its greedy token in-jit, so the host syncs a small
    [B, chunk] int32 grid instead of [B, chunk, V] logits.

    The greedy reduction is exactly `_sample_core`'s `temps <= 0` branch
    (argmax over the true vocab in f32), so position j's token is
    bit-identical to what a plain decode dispatch at that position would
    sample under greedy — the property the acceptance rule's
    token-identity guarantee rests on. Rows with `n_valid == 0` are fed
    zeros and leave the step bit-untouched; each live row's feedback
    buffer entry lands on its LAST valid position's token, keeping the
    buffer consistent for a later plain-decode dispatch against the row.

    Returns (verify_fn, p_shard, c_shard) where
    verify_fn(params, cache, tokens, n_valid, token_buf) ->
    (tokens_out [B, chunk], new_buf [B], new_cache); the step advances
    each row's cache length by its n_valid (draft ingest, verification
    and post-rollback SSM replay are all this one step at different
    n_valid)."""
    rules = S.MeshRules(mesh, serve=params_spec is not None)
    params_specs = (params_spec if params_spec is not None
                    else S.model_state_specs(cfg, with_opt=False))
    p_shard = rules.param_shardings(M.param_axes(cfg), params_specs)
    specs = S.input_specs(cfg, "decode_32k", policy, batch=batch,
                          max_len=max_len, chunk=chunk,
                          kv_block_size=kv_block_size, kv_blocks=kv_blocks)
    c_shard = S.cache_shardings(cfg, rules, specs["cache"], batch)
    vocab, d_model = cfg.vocab, cfg.d_model
    tokens_mode = cfg.input_mode == "tokens"

    def verify_fn(params, cache, tokens, n_valid, token_buf):
        live = jnp.arange(chunk)[None, :] < n_valid[:, None]
        if tokens_mode:
            feed = jnp.where(live, tokens, 0)
        else:
            # embeds-mode stubs feed the one-hot of each token id, zeroed
            # past the valid frontier (same convention as decode_sample)
            oh = jax.nn.one_hot(tokens % d_model, d_model,
                                dtype=jnp.bfloat16)
            feed = oh * live[..., None]
        logits, new_cache = M.decode_step(cfg, params, cache, feed,
                                          policy=policy, shard=rules,
                                          n_valid=n_valid, last_only=False)
        toks = jnp.argmax(logits[..., :vocab].astype(jnp.float32),
                          axis=-1).astype(jnp.int32)
        idx = jnp.clip(n_valid - 1, 0, chunk - 1)
        last = jnp.take_along_axis(toks, idx[:, None], axis=1)[:, 0]
        new_buf = jnp.where(n_valid > 0, last, token_buf)
        return toks, new_buf, new_cache

    return verify_fn, p_shard, c_shard


class ModelExecutor:
    """Device-side execution engine behind the scheduler/engine split."""

    def __init__(self, cfg, params, policy=None, mesh=None, max_slots=4,
                 max_len=256, prefill_chunk=32,
                 kv_block_size: Optional[int] = None,
                 kv_blocks: Optional[int] = None):
        self.cfg = cfg
        self.max_slots = max_slots
        if mesh is None:
            mesh = make_tp_mesh(1)
        self.mesh = mesh
        self.policy = policy
        self.tp = (int(mesh.shape["model"])
                   if "model" in mesh.axis_names else 1)
        # over-allocate by one chunk: a ragged write window [len, len+chunk)
        # must stay in bounds for every row with len < max_len (see
        # layers.ragged_cache_update)
        alloc = max_len + prefill_chunk
        self.alloc = alloc
        self._verify_step = None
        self.verify_chunk = 0
        self.cache = M.init_cache(cfg, max_slots, alloc, policy,
                                  kv_block_size=kv_block_size,
                                  kv_blocks=kv_blocks)
        self.paged = "block_tables" in self.cache
        self.kv_block_size = kv_block_size if self.paged else None
        self.has_ssm = "ssm" in self.cache
        self.num_blocks = (int(self.cache["kv"]["k"].shape[1])
                           if self.paged else 0)
        self.step_cache_key = step_cache_key(
            cfg, policy, mesh, max_slots, alloc, prefill_chunk, params,
            kv_block_size if self.paged else None,
            self.num_blocks if self.paged else None)
        (self._prefill, self._decode_sample, self._seed, p_shard,
         c_shard) = _compiled_steps(
            cfg, policy, mesh, max_slots, alloc, prefill_chunk, params,
            kv_block_size if self.paged else None,
            self.num_blocks if self.paged else None)
        # place params/cache exactly where the compiled steps expect them —
        # each tick's dispatch then moves zero bytes between shards
        self.params = jax.device_put(params, p_shard)
        self.cache = jax.device_put(self.cache, c_shard)
        # physical-block -> shard mapping (the pool partitions its block
        # axis contiguously, so shard = blk // blocks_per_shard); when NB
        # doesn't divide tp the sharding fell back to replicated and the
        # pool is effectively single-shard
        self.pool_shards = (self.tp if self.paged and self.tp > 1
                            and self.num_blocks % self.tp == 0 else 1)
        self.blocks_per_shard = (self.num_blocks // self.pool_shards
                                 if self.pool_shards else 0)
        # device-resident per-slot last-sampled-token feedback buffer,
        # replicated: each shard reads its own copy next tick (no per-tick
        # host sync, no cross-shard fetch)
        self._token_buf = jax.device_put(
            jnp.zeros((max_slots,), jnp.int32),
            NamedSharding(mesh, P()))
        # host mirrors of the device-side control arrays: admission and
        # block allocation write here, `flush` applies each tick's
        # mutations as ONE device update per array (never one dispatch
        # per admitted slot or per allocated block)
        self._lengths_host = np.zeros((max_slots,), np.int32)
        self._lengths_dirty = False
        if self.paged:
            mb = self.cache["block_tables"].shape[1]
            # sentinel num_blocks = unallocated (gathers read zeros, the
            # fused kernel zeroes the staged block) — see model.init_cache
            self._tables_host = np.full((max_slots, mb), self.num_blocks,
                                        np.int32)
            self._tables_dirty = False
        self._ssm_reset_rows: List[int] = []
        self.h2d_updates = 0         # control-array device writes (flushes)
        self.cow_copies = 0

    # -- shard topology ------------------------------------------------------

    def shard_of_block(self, blk: int) -> int:
        """Which `model`-axis shard physically holds pool block `blk`."""
        return blk // self.blocks_per_shard if self.pool_shards > 1 else 0

    def device_bytes(self) -> dict:
        """Per-device resident bytes {weight_bytes, kv_bytes}: the sum of
        each array's LOCAL shard size, i.e. what one device actually
        stores — sharded leaves count 1/tp of their global footprint,
        replicated leaves count in full."""
        def local(a):
            return (math.prod(a.sharding.shard_shape(a.shape))
                    * a.dtype.itemsize)

        wb = sum(local(a) for a in jax.tree.leaves(self.params))
        kv = self.cache["kv"] if "kv" in self.cache else {}
        kb = sum(local(a) for a in jax.tree.leaves(kv))
        return {"weight_bytes": int(wb), "kv_bytes": int(kb)}

    # -- mirror-write protocol (the scheduler's view of the device) ---------

    def set_length(self, row: int, value: int):
        self._lengths_host[row] = value
        self._lengths_dirty = True

    def write_table(self, row: int, idx: int, blk: int):
        self._tables_host[row, idx] = blk
        self._tables_dirty = True

    def reset_table_row(self, row: int):
        self._tables_host[row, :] = self.num_blocks
        self._tables_dirty = True

    def reset_ssm_row(self, row: int):
        self._ssm_reset_rows.append(row)

    def clear_table_entry(self, row: int, idx: int):
        """Return one block-table entry to the sentinel (speculative
        rollback just dropped the block past the accepted frontier)."""
        self._tables_host[row, idx] = self.num_blocks
        self._tables_dirty = True

    def fork_block(self, src: int, dst: int):
        """Copy-on-write fork of one pool block (codes AND paged scales)."""
        self.cache = M.copy_pool_blocks(
            self.cache, np.asarray([src], np.int32),
            np.asarray([dst], np.int32))
        self.cow_copies += 1

    def flush(self):
        """Apply this tick's admission / allocation mutations to the device
        control arrays — at most one update per array per tick, however
        many slots were admitted or blocks claimed."""
        if self._ssm_reset_rows:
            rows = np.asarray(sorted(set(self._ssm_reset_rows)), np.int32)
            self.cache["ssm"] = tuple(
                a.at[:, rows].set(jnp.zeros((), a.dtype))
                for a in self.cache["ssm"])
            self._ssm_reset_rows.clear()
            self.h2d_updates += 1
        # upload COPIES of the mirrors: the CPU client may alias a numpy
        # buffer instead of copying it, and the mirrors keep mutating
        # while the step that reads the upload is still in flight
        if self._lengths_dirty:
            self.cache["lengths"] = jnp.asarray(self._lengths_host.copy())
            self._lengths_dirty = False
            self.h2d_updates += 1
        if self.paged and self._tables_dirty:
            self.cache["block_tables"] = jnp.asarray(self._tables_host.copy())
            self._tables_dirty = False
            self.h2d_updates += 1

    # -- device dispatches (all return un-synced device arrays) -------------

    def prefill(self, row: int, tokens, take: int):
        """One [1, chunk] chunked-prefill dispatch against slot `row`;
        returns that row's last-valid logits [V*] (device)."""
        lg, self.cache = self._prefill(
            self.params, self.cache, tokens,
            jnp.asarray([take], jnp.int32), jnp.int32(row))
        self._lengths_host[row] += take      # mirror the step's +take
        return lg[0]

    def decode_and_sample(self, n_valid: np.ndarray, keys, temps, topks):
        """One fused pool-decode + sample dispatch. `n_valid` [B] host
        array marks frontier rows; returns the sampled tokens [B]
        (device, unsynced) — valid rows' entries are real samples, the
        rest is garbage the caller ignores."""
        toks, self._token_buf, self.cache = self._decode_sample(
            self.params, self.cache, self._token_buf,
            jnp.asarray(n_valid), keys, temps, topks)
        self._lengths_host[n_valid > 0] += 1  # mirror the step's +1
        return toks

    def seed_tokens(self, rows: List[int], logits_rows, keys, temps, topks):
        """Sample first tokens for rows that finished prefill this tick
        and scatter them into the feedback buffer; returns tokens [R]
        (device, unsynced)."""
        toks, self._token_buf = self._seed(
            self._token_buf, jnp.asarray(np.asarray(rows, np.int32)),
            jnp.stack(logits_rows), keys, temps, topks)
        return toks

    def peek_logits(self, n_valid: np.ndarray, policy=None, tokens=None):
        """Logits [B, V*] of the next decode step on the current cache and
        token buffer, computed without advancing either, under `policy`
        (default: the executor's own). `tokens` [B] replaces the token
        buffer as the step's input (to feed two engines the same tokens).
        Rows with `n_valid == 0` are fed token 0, as in the fused decode
        step. Each policy compiles one extra serve step: this is for
        checks (backend-vs-backend logits on identical state), not for
        the serving loop."""
        if self.cfg.input_mode != "tokens":
            raise NotImplementedError("peek_logits feeds token ids")
        policy = self.policy if policy is None else policy
        key = ("peek", self.step_cache_key, policy)
        if key not in _STEP_CACHE:
            fn, _, _, in_sh, out_sh = S.build_serve_step(
                self.cfg, self.mesh, policy, batch=self.max_slots,
                max_len=self.alloc, chunk=1,
                kv_block_size=self.kv_block_size,
                kv_blocks=self.num_blocks if self.paged else None,
                params_spec=jax.eval_shape(lambda: self.params))
            _STEP_CACHE[key] = _jit(
                lambda p, c, t, n: fn(p, c, t, n)[0],
                in_shardings=in_sh, out_shardings=out_sh[0])
        nv = jnp.asarray(np.asarray(n_valid, np.int32))
        feed = (self._token_buf if tokens is None
                else jnp.asarray(np.asarray(tokens, np.int32)))
        tokens = jnp.where(nv > 0, feed, 0)[:, None]
        return _STEP_CACHE[key](self.params, self.cache, tokens, nv)

    # -- speculative decoding (chunked verify + rollback support) -----------

    def ensure_verify_step(self, chunk: int):
        """Compile (or fetch from the shared step cache) the chunked
        verify step at width `chunk` = k+1; idempotent, and cached across
        executor instances exactly like the main step triple."""
        if self.verify_chunk == chunk:
            return
        key = ("verify", self.step_cache_key, chunk)
        if key not in _STEP_CACHE:
            pspec = jax.eval_shape(lambda: self.params)
            fn, p_shard, c_shard = build_verify_step(
                self.cfg, self.mesh, self.policy, batch=self.max_slots,
                max_len=self.alloc, chunk=chunk,
                kv_block_size=self.kv_block_size,
                kv_blocks=self.num_blocks if self.paged else None,
                params_spec=pspec)
            rep = NamedSharding(self.mesh, P())
            _STEP_CACHE[key] = _jit(
                fn, donate_argnums=(1, 4),
                in_shardings=(p_shard, c_shard, rep, rep, rep),
                out_shardings=(rep, rep, c_shard))
        self._verify_step = _STEP_CACHE[key]
        self.verify_chunk = chunk

    def verify(self, tokens: np.ndarray, n_valid: np.ndarray):
        """One chunked verify dispatch: explicit token grid [B, chunk]
        (draft proposals / catch-up replay) with per-row valid counts;
        returns per-position greedy tokens [B, chunk] (device, unsynced).
        Mirrors the step's per-row `+= n_valid` length advance."""
        nv = np.asarray(n_valid, np.int32)
        toks, self._token_buf, self.cache = self._verify_step(
            self.params, self.cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(nv), self._token_buf)
        self._lengths_host += nv             # mirror the step's +n_valid
        return toks

    def checkpoint_ssm(self):
        """Snapshot the recurrent SSM/conv state ahead of a speculative
        dispatch. The decode steps donate the cache, so the snapshot must
        be real copies — not aliases of soon-invalidated buffers."""
        return tuple(jnp.array(a, copy=True) for a in self.cache["ssm"])

    def restore_ssm_rows(self, rows: List[int], saved):
        """Rewind `rows`' recurrent state to a `checkpoint_ssm` snapshot.
        A KV window truncates by clamping the length mirror, but a
        recurrent carry has already folded the rejected draft positions
        in — the only rollback is restore-then-replay."""
        r = jnp.asarray(np.asarray(sorted(rows), np.int32))
        self.cache["ssm"] = tuple(
            a.at[:, r].set(s[:, r])
            for a, s in zip(self.cache["ssm"], saved))
