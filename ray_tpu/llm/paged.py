"""Paged KV cache: block-granular memory for continuous batching.

Reference capability: vLLM's PagedAttention block tables (the engine the
reference wraps, vllm_models.py:125-139) — the slot cache reserves
max_model_len tokens per slot up front, so HBM caps max_num_seqs at
slots x max_model_len x layers; paging shares one block pool across slots and
allocates per BLOCK_SIZE tokens, so many short sequences (or few long ones) fit
the same memory. All shapes stay static for XLA: the pool is
[L, num_blocks, block, kv_heads, head_dim], each slot owns a fixed-width block
table [max_blocks] of pool indices, and reads gather / writes scatter through
the table.

Host-side: _BlockManager hands out pool indices; when the pool is exhausted the
engine preempts the youngest request and re-prefills it later (vLLM's
recompute preemption).
"""
from __future__ import annotations

import functools
import hashlib
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.models.config import ModelConfig

from . import sampling


class PagedState(NamedTuple):
    """Device-resident paged serving state.

    k/v: [L, num_blocks, block_size, kv_heads, head_dim] — the shared pool.
    block_tables: [slots, max_blocks] int32 pool indices (junk entries are
        masked by lengths at read time).
    lengths: [slots] int32 tokens cached per slot.
    """

    k: jax.Array
    v: jax.Array
    block_tables: jax.Array
    lengths: jax.Array


POOL_SPEC = P(None, None, None, "tp", None)
# dp>1: the BLOCK axis shards over dp — each replica owns an independent pool
# partition (plus its own scratch block) and its slots' tables hold replica-
# LOCAL block ids; tables/lengths shard over dp on the slot axis.
POOL_SPEC_DP = P(None, "dp", None, "tp", None)
TABLE_SPEC_DP = P("dp", None)
LENGTHS_SPEC_DP = P("dp")
# pp>1: the LAYER axis shards over pp — each stage holds its layers' slice of
# the block pool (the fitting-a-bigger-model point of inference pp); tables/
# lengths are shared (block ids are layer-independent). With dp too, the block
# axis additionally shards over dp (independent per-replica partitions, as in
# POOL_SPEC_DP) and tables/lengths shard over dp on the slot axis.
POOL_SPEC_PP = P("pp", None, None, "tp", None)
POOL_SPEC_PP_DP = P("pp", "dp", None, "tp", None)


def _dp_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get("dp", 1))


def _pp_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get("pp", 1))


def init_paged_state(cfg: ModelConfig, slots: int, max_len: int, num_blocks: int,
                     block_size: int, mesh: Optional[Mesh] = None) -> PagedState:
    """Each pool (partition) gets ONE extra physical block (its last index):
    inactive slots' decode writes are redirected there — their block-table
    entries may reference blocks already released and re-owned by other
    requests. With dp>1 `num_blocks` is the TOTAL across replicas; each replica
    owns num_blocks/dp blocks + a scratch, and the block axis shards over dp
    (vLLM analogue: one independent KV pool per dp engine replica)."""
    dp = _dp_size(mesh)
    max_blocks = max_len // block_size
    if dp > 1:
        if num_blocks % dp or slots % dp:
            raise ValueError(
                f"num_blocks ({num_blocks}) and slots ({slots}) must divide by "
                f"data_parallel_size ({dp})")
        n_block_axis = num_blocks + dp  # one scratch per replica partition
    else:
        n_block_axis = num_blocks + 1
    shape = (cfg.n_layers, n_block_axis, block_size, cfg.n_kv_heads, cfg.head_dim)
    dtype = cfg.activation_dtype
    k = jnp.zeros(shape, dtype)
    v = jnp.zeros(shape, dtype)
    bt = jnp.zeros((slots, max_blocks), jnp.int32)
    lengths = jnp.zeros((slots,), jnp.int32)
    if mesh is not None:
        pp = _pp_size(mesh)
        if dp > 1 and pp > 1:
            pool_spec = POOL_SPEC_PP_DP
        elif dp > 1:
            pool_spec = POOL_SPEC_DP
        elif pp > 1:
            pool_spec = POOL_SPEC_PP
        else:
            pool_spec = POOL_SPEC
        k = jax.device_put(k, NamedSharding(mesh, pool_spec))
        v = jax.device_put(v, NamedSharding(mesh, pool_spec))
        bt = jax.device_put(bt, NamedSharding(
            mesh, TABLE_SPEC_DP if dp > 1 else P()))
        lengths = jax.device_put(lengths, NamedSharding(
            mesh, LENGTHS_SPEC_DP if dp > 1 else P()))
    return PagedState(k=k, v=v, block_tables=bt, lengths=lengths)


class _BlockManager:
    """Host-side free list + per-slot allocation bookkeeping, with a
    prefix cache (reference: vLLM automatic prefix caching): full prompt
    blocks are content-addressed by a hash CHAIN (block key = H(parent key,
    block tokens)), shared across slots via refcounts, and kept around at
    refcount 0 until the pool needs the space (LRU eviction)."""

    def __init__(self, num_blocks: int, block_size: int, max_blocks_per_slot: int,
                 slots: int, enable_prefix_caching: bool = True):
        self.block_size = block_size
        self.max_blocks = max_blocks_per_slot
        self.total_blocks = num_blocks
        self.free: List[int] = list(range(num_blocks))
        self.owned: List[List[int]] = [[] for _ in range(slots)]  # includes shared
        self.shared: List[List[int]] = [[] for _ in range(slots)]  # shared subset
        self.enable_prefix_caching = enable_prefix_caching
        self.cached: Dict[bytes, int] = {}  # chain key -> block id
        self.block_key: Dict[int, bytes] = {}
        self.refs: Dict[int, int] = {}  # cached block id -> live references
        self._lru: Dict[int, int] = {}  # ref-0 cached block -> last-use tick
        self._tick = 0
        self.hit_tokens = 0  # metrics: prompt tokens served from the cache

    @staticmethod
    def chain_keys(prompt: List[int], block_size: int) -> List[bytes]:
        """Hash-chain keys for each FULL block of the prompt."""
        keys = []
        parent = b""
        for start in range(0, (len(prompt) // block_size) * block_size, block_size):
            h = hashlib.sha256(parent)
            h.update(np.asarray(prompt[start:start + block_size], np.int64).tobytes())
            parent = h.digest()
            keys.append(parent)
        return keys

    @property
    def num_free(self) -> int:
        # ref-0 cached blocks are reclaimable on demand
        return len(self.free) + len(self._lru)

    def blocks_needed(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    def can_allocate(self, n: int) -> bool:
        return self.num_free >= n

    def _take_free(self) -> int:
        if self.free:
            return self.free.pop()
        # evict the least-recently-used unreferenced cached block
        victim = min(self._lru, key=self._lru.get)
        self._lru.pop(victim)
        key = self.block_key.pop(victim)
        self.cached.pop(key, None)
        self.refs.pop(victim, None)
        return victim

    def allocate(self, slot: int, n: int) -> List[int]:
        assert self.num_free >= n, "pool exhausted (caller must check/preempt)"
        got = [self._take_free() for _ in range(n)]
        self.owned[slot].extend(got)
        return got

    def match_prefix(self, slot: int, prompt: List[int]) -> List[int]:
        """Attach the longest cached block chain for this prompt to the slot
        (bumping refcounts); returns the matched block ids in order. Always
        leaves >= 1 prompt token uncached so prefill still produces the
        last-token logits."""
        if not self.enable_prefix_caching:
            return []
        usable = len(prompt) - 1  # the final token must be computed
        matched: List[int] = []
        for key in self.chain_keys(prompt[:usable] if usable > 0 else [],
                                   self.block_size):
            bid = self.cached.get(key)
            if bid is None:
                break
            matched.append(bid)
        if matched:
            # round DOWN to a power of two of blocks: every distinct attached
            # count is a fresh XLA specialization of the gather/suffix-prefill
            # programs, so bound them like the prefill buckets do
            matched = matched[: 1 << (len(matched).bit_length() - 1)]
        for bid in matched:
            if self.refs.get(bid, 0) == 0:
                self._lru.pop(bid, None)
            self.refs[bid] = self.refs.get(bid, 0) + 1
        self.owned[slot].extend(matched)
        self.shared[slot].extend(matched)
        return matched

    def register_blocks(self, slot: int, prompt: List[int],
                        block_ids: List[int], skip_blocks: int) -> None:
        """Publish a slot's freshly filled FULL prompt blocks into the cache
        (the slot keeps them as shared from now on)."""
        if not self.enable_prefix_caching:
            return
        keys = self.chain_keys(prompt, self.block_size)
        for i, key in enumerate(keys):
            if i < skip_blocks:
                continue  # already cached (matched prefix)
            if i >= len(block_ids):
                break
            bid = block_ids[i]
            if key in self.cached:
                continue  # raced by an identical prompt; keep ours private
            self.cached[key] = bid
            self.block_key[bid] = key
            self.refs[bid] = self.refs.get(bid, 0) + 1
            if bid in self.owned[slot] and bid not in self.shared[slot]:
                self.shared[slot].append(bid)

    def release(self, slot: int) -> None:
        shared = set(self.shared[slot])
        self._tick += 1
        for bid in self.owned[slot]:
            if bid in shared:
                self.refs[bid] = self.refs.get(bid, 1) - 1
                if self.refs[bid] <= 0:
                    self.refs[bid] = 0
                    self._lru[bid] = self._tick  # reclaimable, still cached
            else:
                self.free.append(bid)
        self.owned[slot] = []
        self.shared[slot] = []

    def slot_capacity(self, slot: int) -> int:
        return len(self.owned[slot]) * self.block_size

    # slot-aware forms (trivial here; _ShardedBlockManager scopes them to the
    # slot's replica pool) — engine call sites use ONLY these where pool
    # locality matters, so dp>1 composes without engine-side branching
    def can_allocate_for(self, slot: int, n: int) -> bool:
        return self.can_allocate(n)

    def num_free_for(self, slot: int) -> int:
        return self.num_free

    def max_fit(self, slot: int) -> int:
        """Largest block count a request in this slot could ever hold."""
        return min(self.total_blocks, self.max_blocks)

    def same_pool(self, slot_a: int, slot_b: int) -> bool:
        return True

    def owned_for(self, slot: int):
        return self.owned[slot]

    def add_hit_tokens(self, slot: int, n: int) -> None:
        self.hit_tokens += n


class _ShardedBlockManager:
    """dp independent per-replica block pools (reference capability: one vLLM
    engine replica per dp rank, each with its own KV pool — here one host-side
    manager per replica partition inside the single engine). Slot s maps to
    replica s // slots_per; handed-out block ids are replica-LOCAL (the device
    tables are read inside the per-replica shard_map body). The prefix cache is
    per-replica too: a cached block can only serve slots whose tables can
    reference its pool partition."""

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_slot: int, slots: int, dp: int,
                 enable_prefix_caching: bool = True):
        assert num_blocks % dp == 0 and slots % dp == 0
        self.dp = dp
        self.block_size = block_size
        self.max_blocks = max_blocks_per_slot
        self.slots_per = slots // dp
        self.per_replica_blocks = num_blocks // dp
        self.subs = [
            _BlockManager(num_blocks // dp, block_size, max_blocks_per_slot,
                          self.slots_per, enable_prefix_caching)
            for _ in range(dp)
        ]

    def _sub(self, slot: int):
        return self.subs[slot // self.slots_per], slot % self.slots_per

    # -- aggregates (metrics / config introspection) --
    @property
    def total_blocks(self) -> int:
        return sum(s.total_blocks for s in self.subs)

    @property
    def num_free(self) -> int:
        return sum(s.num_free for s in self.subs)

    @property
    def hit_tokens(self) -> int:
        return sum(s.hit_tokens for s in self.subs)

    @hit_tokens.setter
    def hit_tokens(self, value: int) -> None:
        # engine increments on prefix hits; attribute the delta to replica 0's
        # counter is wrong — engine uses add_hit_tokens instead. Setter kept
        # only for symmetry with reads; reject silent use.
        raise AttributeError("use add_hit_tokens(slot, n)")

    def add_hit_tokens(self, slot: int, n: int) -> None:
        sub, _ = self._sub(slot)
        sub.hit_tokens += n

    @property
    def cached(self):
        out = {}
        for r, s in enumerate(self.subs):
            for key, bid in s.cached.items():
                out[(r, key)] = bid
        return out

    # -- slot-scoped API --
    def blocks_needed(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    def can_allocate_for(self, slot: int, n: int) -> bool:
        sub, _ = self._sub(slot)
        return sub.can_allocate(n)

    def num_free_for(self, slot: int) -> int:
        sub, _ = self._sub(slot)
        return sub.num_free

    def max_fit(self, slot: int) -> int:
        return min(self.per_replica_blocks, self.max_blocks)

    def same_pool(self, slot_a: int, slot_b: int) -> bool:
        return slot_a // self.slots_per == slot_b // self.slots_per

    def allocate(self, slot: int, n: int):
        sub, local = self._sub(slot)
        return sub.allocate(local, n)

    def release(self, slot: int) -> None:
        sub, local = self._sub(slot)
        sub.release(local)

    def match_prefix(self, slot: int, prompt):
        sub, local = self._sub(slot)
        return sub.match_prefix(local, prompt)

    def register_blocks(self, slot: int, prompt, block_ids, skip_blocks) -> None:
        sub, local = self._sub(slot)
        sub.register_blocks(local, prompt, block_ids, skip_blocks)

    def slot_capacity(self, slot: int) -> int:
        sub, local = self._sub(slot)
        return sub.slot_capacity(local)

    def owned_for(self, slot: int):
        sub, local = self._sub(slot)
        return sub.owned[local]


def make_block_manager(num_blocks: int, block_size: int,
                       max_blocks_per_slot: int, slots: int, dp: int = 1,
                       enable_prefix_caching: bool = True):
    if dp > 1:
        return _ShardedBlockManager(num_blocks, block_size, max_blocks_per_slot,
                                    slots, dp, enable_prefix_caching)
    return _BlockManager(num_blocks, block_size, max_blocks_per_slot, slots,
                         enable_prefix_caching)


# ----------------------------------------------------------------- prefill install

@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("n_blocks",))
def install_prefill(
    state: PagedState,
    k: jax.Array,  # [L, 1, S_pad, KV, HD] from prefill_detached
    v: jax.Array,
    block_ids: jax.Array,  # [n_blocks] int32 pool indices (S_pad = n_blocks*bs)
    true_len: jax.Array,  # scalar int32
    slot: jax.Array,  # scalar int32
    n_blocks: int,
) -> PagedState:
    """Scatter a prompt's KV into its allocated blocks and fill the block table."""
    L = state.k.shape[0]
    bs = state.k.shape[2]
    kb = k[:, 0].reshape(L, n_blocks, bs, *k.shape[3:]).astype(state.k.dtype)
    vb = v[:, 0].reshape(L, n_blocks, bs, *v.shape[3:]).astype(state.v.dtype)
    nk = state.k.at[:, block_ids].set(kb)
    nv = state.v.at[:, block_ids].set(vb)
    table_row = jnp.zeros((state.block_tables.shape[1],), jnp.int32)
    table_row = jax.lax.dynamic_update_slice(table_row, block_ids, (0,))
    bt = state.block_tables.at[slot].set(table_row)
    lengths = state.lengths.at[slot].set(true_len)
    return PagedState(k=nk, v=nv, block_tables=bt, lengths=lengths)


@functools.partial(jax.jit, donate_argnames=("state",))
def append_block(state: PagedState, slot: jax.Array, index: jax.Array,
                 block_id: jax.Array) -> PagedState:
    """Record a newly allocated decode block in a slot's table."""
    bt = state.block_tables.at[slot, index].set(block_id)
    return state._replace(block_tables=bt)


def trim_kv_for_transfer(k, v, n_tokens: int, block_size: int):
    """Trim bucket-padded prefill KV [L, 1, S_pad, ...] before a P/D handoff
    to the smallest power-of-two block count covering n_tokens + 1.

    The bucket-pad tail is attention-masked garbage the decode side re-pads
    on install anyway, so shipping it only burns handoff bandwidth (a short
    prompt in a coarse bucket can transfer several times its real KV).
    Power-of-two block counts keep the decode side's install_prefill compile
    variants log-bounded, exactly as bucketed prefill shapes do."""
    s_pad = k.shape[2]
    blocks = max(1, -(-(n_tokens + 1) // block_size))
    p2 = 1
    while p2 < blocks:
        p2 <<= 1
    s = p2 * block_size
    if s >= s_pad:
        return k, v
    return k[:, :, :s], v[:, :, :s]


# ----------------------------------------------------------------- prefix cache

@functools.partial(jax.jit, static_argnames=("n_blocks",))
def gather_blocks(state: PagedState, block_ids: jax.Array, n_blocks: int):
    """Cached prefix blocks -> contiguous KV context [L, 1, n*bs, KV, HD]."""
    kb = state.k[:, block_ids]  # [L, n, bs, KV, HD]
    vb = state.v[:, block_ids]
    L, _, bs = kb.shape[0], kb.shape[1], kb.shape[2]
    shape = (L, 1, n_blocks * bs) + kb.shape[3:]
    return kb.reshape(shape), vb.reshape(shape)


@functools.partial(jax.jit, static_argnames=("cfg", "n_blocks"))
def prefill_suffix_from_state(params, state: PagedState, block_ids: jax.Array,
                              tokens, true_suffix_len, cfg: ModelConfig,
                              n_blocks: int):
    """gather_blocks + prefill_suffix fused into ONE program: the warm
    (prefix-hit) path previously dispatched gather and suffix separately —
    an extra host->device round trip per request."""
    ctx_k, ctx_v = gather_blocks(state, block_ids, n_blocks)
    return _prefill_suffix_impl(params, ctx_k, ctx_v, tokens,
                                true_suffix_len, cfg)


def _prefill_suffix_impl(params, ctx_k, ctx_v, tokens, true_suffix_len,
                         cfg: ModelConfig):
    """Prefill ONLY the uncached suffix, attending over the cached-prefix KV
    context (reference: vLLM prefix caching skips recomputation of shared
    prompt prefixes). ctx_k/ctx_v: [L, 1, C, KV, HD]; tokens [1, S_pad].
    Returns (k_suffix [L, 1, S_pad, KV, HD], v_suffix, last_logits)."""
    cached_len = ctx_k.shape[2]
    s_pad = tokens.shape[1]
    dtype = cfg.activation_dtype
    pad = ((0, 0), (0, 0), (0, s_pad), (0, 0), (0, 0))
    cache = llama.KVCache(
        k=jnp.pad(ctx_k.astype(dtype), pad), v=jnp.pad(ctx_v.astype(dtype), pad),
        length=jnp.int32(cached_len))
    mask = (jnp.arange(s_pad)[None, :] < true_suffix_len).astype(jnp.float32)
    logits, cache = llama.forward(params, tokens, cfg, cache=cache, token_mask=mask)
    last = logits[0, true_suffix_len - 1].astype(jnp.float32)
    return (cache.k[:, :, cached_len:], cache.v[:, :, cached_len:], last)


prefill_suffix = functools.partial(jax.jit, static_argnames=("cfg",))(
    _prefill_suffix_impl)


@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("n_new",))
def install_with_prefix(
    state: PagedState,
    k_suf: jax.Array,  # [L, 1, S_pad, KV, HD] — suffix KV only
    v_suf: jax.Array,
    new_ids: jax.Array,  # [n_new] pool indices for the suffix
    table_row: jax.Array,  # [max_blocks] full table (cached + new ids, padded)
    true_len: jax.Array,
    slot: jax.Array,
    n_new: int,
) -> PagedState:
    """Install suffix KV into fresh blocks; cached-prefix blocks are already in
    the pool and only need table entries."""
    L = state.k.shape[0]
    bs = state.k.shape[2]
    kb = k_suf[:, 0].reshape(L, n_new, bs, *k_suf.shape[3:]).astype(state.k.dtype)
    vb = v_suf[:, 0].reshape(L, n_new, bs, *v_suf.shape[3:]).astype(state.v.dtype)
    nk = state.k.at[:, new_ids].set(kb)
    nv = state.v.at[:, new_ids].set(vb)
    bt = state.block_tables.at[slot].set(table_row)
    lengths = state.lengths.at[slot].set(true_len)
    return PagedState(k=nk, v=nv, block_tables=bt, lengths=lengths)


# ------------------------------------------------------------------------- decode

def _paged_block(x, lp, cfg: ModelConfig, pk, pv, block_tables, lengths, active):
    """One layer's window against the paged pool for all slots: the shared
    layer math (model_runner._window_core) with a block-table cache adapter.
    Decode is a window of one.

    x [S,W,D]; pk/pv [NB, bs, KV, HD] (this layer's pool); reads gather each
    slot's blocks into [S, max_len, KV, HD] (activation-only — the POOL is what
    lives in HBM persistently), writes scatter the window through the table.
    The engine pre-grows every active slot's table by the window width, so all
    window positions map to owned blocks.
    """
    from .model_runner import _window_core

    s = x.shape[0]
    nb_slot = block_tables.shape[1]
    bs = pk.shape[1]
    max_len = nb_slot * bs
    kvh, hd = cfg.n_kv_heads, cfg.head_dim

    def cache_rw(k_new, v_new, pos):  # pos [S,W]: the window's absolute positions
        # scatter through the block table (distinct active slots own distinct
        # blocks, so writes never collide); INACTIVE slots' tables may point at
        # freed/re-owned blocks, so their writes (and any position past the
        # table) land in the scratch block (the pool's last physical block,
        # never allocated)
        scratch = pk.shape[0] - 1
        blk_idx = pos // bs  # [S,W]
        in_table = blk_idx < nb_slot
        safe_idx = jnp.minimum(blk_idx, nb_slot - 1)
        rows = jnp.arange(s)[:, None]
        write_block = jnp.where(active[:, None] & in_table,
                                block_tables[rows, safe_idx], scratch)
        write_off = pos % bs
        nk = pk.at[write_block, write_off].set(k_new.astype(pk.dtype))
        nv = pv.at[write_block, write_off].set(v_new.astype(pv.dtype))
        ck = nk[block_tables].reshape(s, max_len, kvh, hd)
        cv = nv[block_tables].reshape(s, max_len, kvh, hd)
        return ck, cv, (nk, nv)

    x, (nk, nv) = _window_core(x, lp, cfg, lengths, active, cache_rw)
    return x, nk, nv


def _decode_step_impl(params, k, v, block_tables, lengths, tokens, active,
                      cfg: ModelConfig):
    """One decode step against ONE pool (the whole pool, or — inside the dp
    shard_map — one replica's local shard). Raw arrays in/out so the same math
    serves the single-pool jit and the per-replica body."""
    from .model_runner import _layer_loop

    x = llama.embed_tokens(params, tokens[:, None], cfg)  # [S,1,D]
    x, nk, nv = _layer_loop(
        lambda h, lp, pk, pv: _paged_block(h, lp, cfg, pk, pv, block_tables,
                                           lengths, active),
        x, params["layers"], k, v)
    logits = llama.output_head(params, x, cfg)[:, 0]
    new_lengths = jnp.where(active, lengths + 1, lengths)
    return nk, nv, new_lengths, logits


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def decode_step_paged(
    params,
    state: PagedState,
    tokens: jax.Array,  # [slots] int32
    active: jax.Array,  # [slots] bool
    cfg: ModelConfig,
) -> Tuple[PagedState, jax.Array]:
    """One decode step for every slot against the paged pool."""
    nk, nv, lengths, logits = _decode_step_impl(
        params, state.k, state.v, state.block_tables, state.lengths,
        tokens, active, cfg)
    return PagedState(k=nk, v=nv, block_tables=state.block_tables,
                      lengths=lengths), logits


def _pp_paged_layers(params, state: PagedState, x, active, mesh: Mesh, *,
                     width: int, block_fn):
    """Paged layer pass through the pp schedule, shared by decode (width=1)
    and spec verify (width=W). Unlike the slot variant the whole (stage-local)
    pool rides the scan carry; block_fn(h, lp, pk, pv, bt_mb, ln_mb, act_eff)
    -> (h, pk, pv), where act_eff is False on bubble ticks so those writes
    land in the scratch block."""
    from ray_tpu.llm.model_runner import _layer_loop, _pp_schedule, _pp_shard_map

    m = mesh.shape["pp"]
    nb_slot = state.block_tables.shape[1]

    def inner(layers_local, k_local, v_local, x_local, bt, lengths, active_i):
        s_l = x_local.shape[0]  # this dp replica's slot count
        smb = s_l // m
        x_mb = x_local.reshape(m, smb, width, x_local.shape[-1])

        def step_mb(x_in, kv, jc, valid):
            k, v = kv
            bt_mb = jax.lax.dynamic_slice(bt, (jc * smb, 0), (smb, nb_slot))
            ln_mb = jax.lax.dynamic_slice(lengths, (jc * smb,), (smb,))
            act_mb = (jax.lax.dynamic_slice(active_i, (jc * smb,), (smb,)) > 0)
            act_eff = act_mb & valid  # bubble ticks write only the scratch block

            h, nk, nv = _layer_loop(
                lambda c, lp, pk, pv: block_fn(c, lp, pk, pv, bt_mb, ln_mb, act_eff),
                x_in, layers_local, k, v)
            return h, (nk, nv)

        outs, (k, v) = _pp_schedule(x_mb, (k_local, v_local), step_mb)
        return outs.reshape(s_l, width, outs.shape[-1]), k, v

    return _pp_shard_map(inner, params["layers"], mesh,
                         (state.k, state.v, x, state.block_tables,
                          state.lengths, active.astype(jnp.int32)))


def decode_step_paged_pp(params, state: PagedState, tokens, active,
                         cfg: ModelConfig, mesh: Mesh):
    """Paged decode with the layer stack + pool split across "pp" stages.

    Mirror of model_runner.decode_step_pp on the paged layout: each stage holds
    its L/pp layers and THEIR slice of the block pool (POOL_SPEC_PP); slots
    split into pp microbatches and activations hop stage->stage via ppermute.
    Block tables/lengths are layer-independent, so every stage reads the same
    tables. Bubble ticks run a clipped microbatch with active=False, so their
    scatter lands in the scratch block — no whole-pool select per tick is
    needed to discard them. tp/ep stay GSPMD auto axes inside the stage. With
    dp>1, slots and the block axis additionally shard over dp replicas
    (POOL_SPEC_PP_DP): each replica owns an independent pool partition with
    replica-local block ids and its own scratch (the partition's last block),
    so the manual-region body is unchanged — it just sees local arrays.
    """
    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    s = tokens.shape[0]
    if s % (pp * dp):
        raise ValueError(f"max_num_seqs {s} must be divisible by pp*dp {pp * dp}")

    x = llama.embed_tokens(params, tokens[:, None], cfg)  # [S,1,D]
    h, nk, nv = _pp_paged_layers(
        params, state, x, active, mesh, width=1,
        block_fn=lambda c, lp, pk, pv, bt, ln, ac:
            _paged_block(c, lp, cfg, pk, pv, bt, ln, ac))
    logits = llama.output_head(params, h, cfg)[:, 0]
    lengths = jnp.where(active, state.lengths + 1, state.lengths)
    return PagedState(k=nk, v=nv, block_tables=state.block_tables,
                      lengths=lengths), logits


def spec_verify_step_paged_pp(params, state: PagedState, window, draft_len,
                              active, rng, temperature, top_p, top_k, *,
                              cfg: ModelConfig, mesh: Mesh):
    """Paged speculative verify through the pipeline schedule: the verify
    window is the microbatch payload, each stage holds its layers' pool slice,
    and bubble-tick writes redirect to the scratch block via the same
    active-mask plumbing _paged_block already has. Composes with dp
    (replica pool partitions) exactly like decode_step_paged_pp."""
    from .model_runner import spec_driver

    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    s, w = window.shape
    if s % (pp * dp):
        raise ValueError(f"max_num_seqs {s} must be divisible by pp*dp {pp * dp}")

    def layers_pass(x):  # [S, W, D]
        return _pp_paged_layers(
            params, state, x, active, mesh, width=w,
            block_fn=lambda c, lp, pk, pv, bt, ln, ac:
                _paged_block(c, lp, cfg, pk, pv, bt, ln, ac))

    nk, nv, lengths, greedy, n_acc = spec_driver(
        params, state.k, state.v, state.lengths, window, draft_len, active,
        cfg, rng, temperature, top_p, top_k, layers_pass=layers_pass)
    return PagedState(k=nk, v=nv, block_tables=state.block_tables,
                      lengths=lengths), greedy, n_acc


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def spec_verify_step_paged(
    params,
    state: PagedState,
    window: jax.Array,  # [S,W] int32 — [last_token, draft_1..draft_k]
    draft_len: jax.Array,  # [S] int32
    active: jax.Array,  # [S] bool
    cfg: ModelConfig,
    rng: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
    top_k: jax.Array,
):
    """Speculative verify against the paged pool (see
    model_runner.spec_verify_step for the contract)."""
    from .model_runner import spec_driver

    nk, nv, lengths, greedy, n_acc = spec_driver(
        params, state.k, state.v, state.lengths, window, draft_len, active,
        cfg, rng, temperature, top_p, top_k,
        lambda h, lp, pk, pv: _paged_block(
            h, lp, cfg, pk, pv, state.block_tables, state.lengths, active))
    return PagedState(k=nk, v=nv, block_tables=state.block_tables,
                      lengths=lengths), greedy, n_acc


@functools.partial(
    jax.jit, static_argnames=("cfg", "m", "k", "nmax", "propose_fn"),
    donate_argnames=("state",))
def spec_multi_paged(
    params,
    state: PagedState,
    hist: jax.Array,  # [S, width] int32 — prompt + emitted tokens per slot
    hlen: jax.Array,  # [S] int32
    active: jax.Array,  # [S] bool — FIXED for the whole burst
    cfg: ModelConfig,
    rngs: jax.Array,  # [m] stacked PRNG keys
    temperature: jax.Array,
    top_p: jax.Array,
    top_k: jax.Array,
    m: int,
    k: int,
    nmax: int,
    propose_fn=None,
):
    """m fused speculative windows against the PAGED pool (spec x multi-step x
    paged composed): same propose->verify->accept scan as model_runner.spec_multi
    with block-table writes. Callers pre-grow every active slot's table by
    m*(k+1) tokens — block_tables are frozen across the burst; window positions
    past a slot's table land in the scratch block (never read back, because
    lengths only advance over accepted tokens that DO have table entries)."""
    from .model_runner import propose_ngram_device, spec_multi_impl

    return spec_multi_impl(
        params, state, hist, hlen, active, cfg, rngs, temperature, top_p,
        top_k, m, k, nmax, propose_fn or propose_ngram_device,
        lambda st: lambda x, lp, pk, pv: _paged_block(
            x, lp, cfg, pk, pv, st.block_tables, st.lengths, active),
        lambda st, nk, nv, lengths: PagedState(
            k=nk, v=nv, block_tables=st.block_tables, lengths=lengths))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def decode_multi_paged(
    params,
    state: PagedState,
    tokens: jax.Array,  # [slots] int32
    active: jax.Array,  # [slots] bool — FIXED for the whole burst
    cfg: ModelConfig,
    rngs: jax.Array,  # [K] stacked PRNG keys
    temperature: jax.Array,  # [slots] f32
    top_p: jax.Array,  # [slots] f32
    top_k: jax.Array,  # [slots] i32
    steps_left: jax.Array,  # [slots] int32 — per-slot step budget within K
):
    """K fused decode+sample steps against the paged pool (one host sync per
    burst; vLLM multi-step scheduling). Callers pre-grow every active slot's
    block table by min(K, steps_left[s]) tokens — block_tables are frozen
    across the burst. steps_left makes the burst barrier-free: a slot past its
    own budget goes inactive for the remaining steps (its writes land in the
    scratch block) instead of capping K for the whole batch."""
    def body(carry, xs):
        rng, t = xs
        st, toks = carry
        act_t = active & (t < steps_left)
        st, logits = decode_step_paged(params, st, toks, act_t, cfg)
        nxt = sampling.sample(rng, logits, temperature, top_p, top_k)
        nxt = jnp.where(act_t, nxt, toks).astype(jnp.int32)
        return (st, nxt), nxt

    (state, _), toks_k = jax.lax.scan(
        body, (state, tokens.astype(jnp.int32)),
        (rngs, jnp.arange(rngs.shape[0], dtype=jnp.int32)))
    return state, toks_k


# ------------------------------------------------- data-parallel (dp) composition
#
# kv_layout="paged" with data_parallel_size > 1 (the vLLM capability of one KV
# pool per dp engine replica, here inside ONE SPMD program): every paged device
# op runs under a shard_map whose manual axis is "dp" — each replica owns an
# independent pool partition + scratch block, its slots' tables hold replica-
# LOCAL block ids, and decode/verify touch no cross-replica data at all (tp
# stays a GSPMD auto axis inside the body). Slot-targeted ops (installs, table
# appends) are replica-masked: non-owners redirect their writes to their own
# scratch block, so nothing is ever selected over the full pool.

POOL_DP = P(None, "dp", None, None, None)  # manual-axis view of POOL_SPEC_DP
TABLE_DP = P("dp", None)
VEC_DP = P("dp")


def _rep_specs(tree):
    """Replicated-in-dp specs for a params pytree (tp shardings stay auto)."""
    return jax.tree.map(lambda _: P(), tree)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"),
                   donate_argnames=("state",))
def decode_step_paged_dp(params, state: PagedState, tokens, active,
                         cfg: ModelConfig, mesh: Mesh):
    from ray_tpu.parallel.sharding import manual_axes

    def body(p, k, v, bt, ln, toks, act):
        return _decode_step_impl(p, k, v, bt, ln, toks, act, cfg)

    with manual_axes("dp"):
        nk, nv, lengths, logits = jax.shard_map(
            body, mesh=mesh,
            in_specs=(_rep_specs(params), POOL_DP, POOL_DP, TABLE_DP, VEC_DP,
                      VEC_DP, VEC_DP),
            out_specs=(POOL_DP, POOL_DP, VEC_DP, P("dp", None)),
            axis_names={"dp"},
        )(params, state.k, state.v, state.block_tables, state.lengths,
          tokens, active)
    return PagedState(k=nk, v=nv, block_tables=state.block_tables,
                      lengths=lengths), logits


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"),
                   donate_argnames=("state",))
def decode_multi_paged_dp(params, state: PagedState, tokens, active,
                          cfg: ModelConfig, rngs, temperature, top_p, top_k,
                          steps_left, mesh: Mesh):
    from ray_tpu.parallel.sharding import manual_axes

    def body(p, k, v, bt, ln, toks, act, rr, tt, tp_, tk, sl):
        # distinct sampling streams per replica
        rr = jax.vmap(lambda r: jax.random.fold_in(r, jax.lax.axis_index("dp")))(rr)

        def step(carry, xs):
            rng, t_i = xs
            kk, vv, lln, t = carry
            act_t = act & (t_i < sl)
            kk, vv, lln, logits = _decode_step_impl(p, kk, vv, bt, lln, t,
                                                    act_t, cfg)
            nxt = sampling.sample(rng, logits, tt, tp_, tk)
            nxt = jnp.where(act_t, nxt, t).astype(jnp.int32)
            return (kk, vv, lln, nxt), nxt

        (kk, vv, lln, _), toks_k = jax.lax.scan(
            step, (k, v, ln, toks.astype(jnp.int32)),
            (rr, jnp.arange(rr.shape[0], dtype=jnp.int32)))
        return kk, vv, lln, toks_k

    with manual_axes("dp"):
        nk, nv, lengths, toks_k = jax.shard_map(
            body, mesh=mesh,
            in_specs=(_rep_specs(params), POOL_DP, POOL_DP, TABLE_DP, VEC_DP,
                      VEC_DP, VEC_DP, P(), VEC_DP, VEC_DP, VEC_DP, VEC_DP),
            out_specs=(POOL_DP, POOL_DP, VEC_DP, P(None, "dp")),
            axis_names={"dp"},
        )(params, state.k, state.v, state.block_tables, state.lengths,
          tokens, active, rngs, temperature, top_p, top_k, steps_left)
    return PagedState(k=nk, v=nv, block_tables=state.block_tables,
                      lengths=lengths), toks_k


def _install_dp(state: PagedState, k, v, new_ids, table_row, true_len, slot,
                n_new: int, mesh: Mesh, slots_per: int):
    """Shared dp-sharded install: scatter n_new fresh KV blocks + set the
    slot's table row and length — only on the OWNING replica's shard;
    non-owners redirect the scatter into their own scratch block (cheap, never
    read) so nothing is ever selected over the full pool."""
    from ray_tpu.parallel.sharding import manual_axes

    replica = slot // slots_per
    local_slot = slot % slots_per

    def body(pk, pv, bt, ln, kk, vv, ids, row):
        mine = jax.lax.axis_index("dp") == replica
        scratch = pk.shape[1] - 1
        ids_eff = jnp.where(mine, ids, scratch)
        L = pk.shape[0]
        bs = pk.shape[2]
        kb = kk[:, 0].reshape(L, n_new, bs, *kk.shape[3:]).astype(pk.dtype)
        vb = vv[:, 0].reshape(L, n_new, bs, *vv.shape[3:]).astype(pv.dtype)
        nk = pk.at[:, ids_eff].set(kb)
        nv = pv.at[:, ids_eff].set(vb)
        nbt = bt.at[local_slot].set(jnp.where(mine, row, bt[local_slot]))
        nln = ln.at[local_slot].set(jnp.where(mine, true_len, ln[local_slot]))
        return nk, nv, nbt, nln

    with manual_axes("dp"):
        nk, nv, bt, ln = jax.shard_map(
            body, mesh=mesh,
            in_specs=(POOL_DP, POOL_DP, TABLE_DP, VEC_DP, P(), P(), P(), P()),
            out_specs=(POOL_DP, POOL_DP, TABLE_DP, VEC_DP),
            axis_names={"dp"},
        )(state.k, state.v, state.block_tables, state.lengths,
          k, v, new_ids, table_row)
    return PagedState(k=nk, v=nv, block_tables=bt, lengths=ln)


@functools.partial(jax.jit, static_argnames=("n_blocks", "mesh", "slots_per"),
                   donate_argnames=("state",))
def install_prefill_dp(state: PagedState, k, v, block_ids, true_len, slot,
                       n_blocks: int, mesh: Mesh, slots_per: int):
    """install_prefill with the pool dp-sharded: the table row is just the
    fresh block ids (whole-prompt install)."""
    row = jnp.zeros((state.block_tables.shape[1],), jnp.int32)
    row = jax.lax.dynamic_update_slice(row, block_ids, (0,))
    return _install_dp(state, k, v, block_ids, row, true_len, slot,
                       n_new=n_blocks, mesh=mesh, slots_per=slots_per)


@functools.partial(jax.jit, static_argnames=("n_new", "mesh", "slots_per"),
                   donate_argnames=("state",))
def install_with_prefix_dp(state: PagedState, k_suf, v_suf, new_ids, table_row,
                           true_len, slot, n_new: int, mesh: Mesh,
                           slots_per: int):
    """install_with_prefix with the pool dp-sharded: only the suffix KV
    scatters (the cached-prefix blocks are already in the replica's pool); the
    caller-built table row carries cached + new ids."""
    return _install_dp(state, k_suf, v_suf, new_ids, table_row, true_len, slot,
                       n_new=n_new, mesh=mesh, slots_per=slots_per)


@functools.partial(jax.jit, static_argnames=("mesh", "slots_per"),
                   donate_argnames=("state",))
def append_block_dp(state: PagedState, slot, index, block_id, mesh: Mesh,
                    slots_per: int):
    from ray_tpu.parallel.sharding import manual_axes

    replica = slot // slots_per
    local_slot = slot % slots_per

    def body(bt):
        mine = jax.lax.axis_index("dp") == replica
        new = bt.at[local_slot, index].set(block_id)
        return jnp.where(mine, new, bt)

    with manual_axes("dp"):
        bt = jax.shard_map(body, mesh=mesh, in_specs=(TABLE_DP,),
                           out_specs=TABLE_DP, axis_names={"dp"},
                           )(state.block_tables)
    return state._replace(block_tables=bt)


@functools.partial(jax.jit, static_argnames=("cfg", "n_blocks", "mesh",
                                             "slots_per"))
def prefill_suffix_from_state_dp(params, state: PagedState, block_ids, tokens,
                                 true_suffix_len, cfg: ModelConfig,
                                 n_blocks: int, mesh: Mesh, slots_per: int,
                                 slot=None):
    """Prefix-cache warm path under dp: the owning replica gathers its cached
    blocks (others contribute zeros), a psum replicates the context, and the
    suffix prefill runs in auto mode — still ONE device dispatch."""
    from ray_tpu.parallel.sharding import manual_axes

    replica = slot // slots_per

    def gather(pk, pv, ids):
        mine = jax.lax.axis_index("dp") == replica
        ids_eff = jnp.where(mine, ids, pk.shape[1] - 1)
        kb = jnp.where(mine, pk[:, ids_eff], 0)
        vb = jnp.where(mine, pv[:, ids_eff], 0)
        return jax.lax.psum(kb, "dp"), jax.lax.psum(vb, "dp")

    with manual_axes("dp"):
        kb, vb = jax.shard_map(
            gather, mesh=mesh, in_specs=(POOL_DP, POOL_DP, P()),
            out_specs=(P(), P()), axis_names={"dp"},
        )(state.k, state.v, block_ids)
    L, _, bs = kb.shape[0], kb.shape[1], kb.shape[2]
    shape = (L, 1, n_blocks * bs) + kb.shape[3:]
    return _prefill_suffix_impl(params, kb.reshape(shape), vb.reshape(shape),
                                tokens, true_suffix_len, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"),
                   donate_argnames=("state",))
def spec_verify_step_paged_dp(params, state: PagedState, window, draft_len,
                              active, cfg: ModelConfig, rng, temperature,
                              top_p, top_k, mesh: Mesh):
    from ray_tpu.parallel.sharding import manual_axes

    from .model_runner import spec_driver

    def body(p, k, v, bt, ln, win, dl, act, rr, tt, tp_, tk):
        rr = jax.random.fold_in(rr, jax.lax.axis_index("dp"))
        nk, nv, lengths, greedy, n_acc = spec_driver(
            p, k, v, ln, win, dl, act, cfg, rr, tt, tp_, tk,
            lambda h, lp, pk, pv: _paged_block(h, lp, cfg, pk, pv,
                                                      bt, ln, act))
        return nk, nv, lengths, greedy, n_acc

    with manual_axes("dp"):
        nk, nv, lengths, greedy, n_acc = jax.shard_map(
            body, mesh=mesh,
            in_specs=(_rep_specs(params), POOL_DP, POOL_DP, TABLE_DP, VEC_DP,
                      TABLE_DP, VEC_DP, VEC_DP, P(), VEC_DP, VEC_DP, VEC_DP),
            out_specs=(POOL_DP, POOL_DP, VEC_DP, TABLE_DP, VEC_DP),
            axis_names={"dp"},
        )(params, state.k, state.v, state.block_tables, state.lengths,
          window, draft_len, active, rng, temperature, top_p, top_k)
    return PagedState(k=nk, v=nv, block_tables=state.block_tables,
                      lengths=lengths), greedy, n_acc


@functools.partial(
    jax.jit, static_argnames=("cfg", "m", "k", "nmax", "mesh"),
    donate_argnames=("state",))
def spec_multi_paged_dp(params, state: PagedState, hist, hlen, active,
                        cfg: ModelConfig, rngs, temperature, top_p, top_k,
                        m: int, k: int, nmax: int, mesh: Mesh):
    from ray_tpu.parallel.sharding import manual_axes

    from .model_runner import propose_ngram_device, spec_multi_impl

    def body(p, pk, pv, bt, ln, hh, hl, act, rr, tt, tp_, tk):
        rr = jax.vmap(lambda r: jax.random.fold_in(r, jax.lax.axis_index("dp")))(rr)
        st = PagedState(k=pk, v=pv, block_tables=bt, lengths=ln)
        st, toks_m, acc_m, drafted_m = spec_multi_impl(
            p, st, hh, hl, act, cfg, rr, tt, tp_, tk, m, k, nmax,
            propose_ngram_device,
            lambda s: lambda x, lp, kk, vv: _paged_block(
                x, lp, cfg, kk, vv, s.block_tables, s.lengths, act),
            lambda s, nk, nv, lengths: PagedState(
                k=nk, v=nv, block_tables=s.block_tables, lengths=lengths))
        return st.k, st.v, st.lengths, toks_m, acc_m, drafted_m

    with manual_axes("dp"):
        nk, nv, lengths, toks_m, acc_m, drafted_m = jax.shard_map(
            body, mesh=mesh,
            in_specs=(_rep_specs(params), POOL_DP, POOL_DP, TABLE_DP, VEC_DP,
                      TABLE_DP, VEC_DP, VEC_DP, P(), VEC_DP, VEC_DP, VEC_DP),
            out_specs=(POOL_DP, POOL_DP, VEC_DP, P(None, "dp", None),
                       P(None, "dp"), P(None, "dp")),
            axis_names={"dp"},
        )(params, state.k, state.v, state.block_tables, state.lengths,
          hist, hlen, active, rngs, temperature, top_p, top_k)
    return PagedState(k=nk, v=nv, block_tables=state.block_tables,
                      lengths=lengths), toks_m, acc_m, drafted_m


class PagedOps:
    """Engine-facing dispatch over the paged device ops: dp=1 delegates to the
    single-pool jits; dp>1 routes through the shard_map variants (the engine's
    call sites stay layout- and mesh-agnostic)."""

    def __init__(self, cfg: ModelConfig, mesh: Optional[Mesh], slots: int):
        self.cfg = cfg
        self.mesh = mesh
        self.dp = _dp_size(mesh)
        self.pp = _pp_size(mesh)
        self.slots_per = slots // max(self.dp, 1)
        if self.pp > 1:
            # jit + pool donation for the hot decode loop (parity with the
            # decode_step_paged jit and the engine's slot-pp _decode_pp_jit)
            self._decode_pp = jax.jit(
                functools.partial(decode_step_paged_pp, cfg=cfg, mesh=mesh),
                donate_argnames=("state",))
            self._spec_pp = jax.jit(
                functools.partial(spec_verify_step_paged_pp, cfg=cfg, mesh=mesh),
                donate_argnames=("state",))

    def install_prefill(self, state, k, v, block_ids, true_len, slot, n_blocks):
        if self.dp > 1:
            return install_prefill_dp(state, k, v, block_ids, true_len, slot,
                                      n_blocks=n_blocks, mesh=self.mesh,
                                      slots_per=self.slots_per)
        return install_prefill(state, k, v, block_ids, true_len, slot,
                               n_blocks=n_blocks)

    def install_with_prefix(self, state, k_suf, v_suf, new_ids, table_row,
                            true_len, slot, n_new):
        if self.dp > 1:
            return install_with_prefix_dp(state, k_suf, v_suf, new_ids,
                                          table_row, true_len, slot,
                                          n_new=n_new, mesh=self.mesh,
                                          slots_per=self.slots_per)
        return install_with_prefix(state, k_suf, v_suf, new_ids, table_row,
                                   true_len, slot, n_new=n_new)

    def append_block(self, state, slot, index, block_id):
        if self.dp > 1:
            return append_block_dp(state, slot, index, block_id,
                                   mesh=self.mesh, slots_per=self.slots_per)
        return append_block(state, slot, index, block_id)

    def prefill_suffix_from_state(self, params, state, block_ids, tokens,
                                  true_suffix_len, n_blocks, slot):
        if self.dp > 1:
            return prefill_suffix_from_state_dp(
                params, state, block_ids, tokens, true_suffix_len, self.cfg,
                n_blocks=n_blocks, mesh=self.mesh, slots_per=self.slots_per,
                slot=slot)
        return prefill_suffix_from_state(params, state, block_ids, tokens,
                                         true_suffix_len, self.cfg,
                                         n_blocks=n_blocks)

    def decode_step(self, params, state, tokens, active):
        if self.pp > 1:
            # handles dp>1 too (slots + pool partition per replica inside the
            # same manual region)
            return self._decode_pp(params, state, tokens, active)
        if self.dp > 1:
            return decode_step_paged_dp(params, state, tokens, active,
                                        self.cfg, self.mesh)
        return decode_step_paged(params, state, tokens, active, self.cfg)

    def decode_multi(self, params, state, tokens, active, rngs, temperature,
                     top_p, top_k, steps_left):
        if self.dp > 1:
            return decode_multi_paged_dp(params, state, tokens, active,
                                         self.cfg, rngs, temperature, top_p,
                                         top_k, steps_left, mesh=self.mesh)
        return decode_multi_paged(params, state, tokens, active, self.cfg,
                                  rngs, temperature, top_p, top_k, steps_left)

    def spec_verify(self, params, state, window, draft_len, active, rng,
                    temperature, top_p, top_k):
        if self.pp > 1:
            # handles dp>1 too (same manual region as the pp decode)
            return self._spec_pp(params, state, window, draft_len, active,
                                 rng, temperature, top_p, top_k)
        if self.dp > 1:
            return spec_verify_step_paged_dp(params, state, window, draft_len,
                                             active, self.cfg, rng,
                                             temperature, top_p, top_k,
                                             mesh=self.mesh)
        return spec_verify_step_paged(params, state, window, draft_len, active,
                                      self.cfg, rng, temperature, top_p, top_k)

    def spec_multi(self, params, state, hist, hlen, active, rngs, temperature,
                   top_p, top_k, m, k, nmax):
        if self.dp > 1:
            return spec_multi_paged_dp(params, state, hist, hlen, active,
                                       self.cfg, rngs, temperature, top_p,
                                       top_k, m=m, k=k, nmax=nmax,
                                       mesh=self.mesh)
        return spec_multi_paged(params, state, hist, hlen, active, self.cfg,
                                rngs, temperature, top_p, top_k, m=m, k=k,
                                nmax=nmax)


# ------------------------------------------------------------------ chunked prefill

def chunked_prefill(params, prompt_ids: List[int], cfg: ModelConfig,
                    chunk: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill a long prompt chunk-at-a-time (reference: vLLM chunked prefill).

    Peak activation memory is one chunk's, not the whole prompt's; the temp KV
    grows to the padded prompt length and is installed into blocks afterwards.
    Returns (k [L,1,S_pad,KV,HD], v, last_logits [vocab] f32)."""
    n = len(prompt_ids)
    s_pad = -(-n // chunk) * chunk
    cache = llama.init_kv_cache(cfg, batch=1, max_len=s_pad,
                                dtype=cfg.activation_dtype)
    last = None
    for start in range(0, s_pad, chunk):
        piece = prompt_ids[start:start + chunk]
        tokens = np.zeros((1, chunk), np.int32)
        tokens[0, : len(piece)] = piece
        logits, cache = _prefill_chunk(params, cache, jnp.asarray(tokens),
                                       jnp.int32(len(piece)), cfg)
        if start < n <= start + chunk:
            last = logits[0, (n - 1) - start].astype(jnp.float32)
    return cache.k, cache.v, last


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _prefill_chunk(params, cache, tokens, true_len, cfg: ModelConfig):
    # pad positions in the final chunk must not claim MoE expert capacity
    # (model_runner.prefill passes the same mask for the same reason)
    mask = (jnp.arange(tokens.shape[1])[None, :] < true_len).astype(jnp.float32)
    logits, cache = llama.forward(params, tokens, cfg, cache=cache, token_mask=mask)
    return logits, cache
