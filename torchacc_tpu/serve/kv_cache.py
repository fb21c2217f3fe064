"""Paged KV cache: the preallocated block pool + host-side allocator.

Memory layout (the vLLM PagedAttention idea expressed as JAX arrays):
ONE preallocated buffer for keys and one for values, each ``[L, NB, BS,
KH*D]`` — layers, blocks, block size, and one token's ROW of all kv
heads.  Rows are contiguous because of who writes them: the decode and
prefill programs bank a token's k/v with an XLA scatter at ``(layer,
block, offset)``, and a ``[KH*D]`` row there is one contiguous window
the compiler updates in place.  (With ``(block_size, head_dim)`` last a
token's ``[KH, D]`` is strided by ``BS*D``; layout assignment then
relayouts the whole pool for the scatter and copies it back for the
kernel — every layer, every step.)  The buffer rides the layer scan's
carry and the paged-attention kernel reads pages straight out of it
through a layer index (ops/paged_attention.py): no program slices a
layer out, copies the pool or re-stacks it.  A sequence's cache is a
list of blocks named by its BLOCK TABLE; sequences of wildly different
lengths share the pool with at most ``block_size - 1`` wasted slots
each, and a finished sequence's blocks return to the free list as soon
as every in-flight iteration that could still write through its table
has resolved (at most ``decode_depth - 1`` iterations —
scheduler._release_matured) — no ``[batch, max_len]`` padding anywhere.

Block 0 is the NULL BLOCK: free decode slots (and masked-out prefill
tail tokens) write their garbage k/v there, so the jitted step needs
no write masking — the standard trick.  It is never handed out by the
allocator.

Prefix sharing (``serve.prefix_cache``): blocks are REFCOUNTED, and a
:class:`PrefixIndex` maps a hash chain over each FULL block of prompt
tokens (``key_i = blake2b(key_{i-1} || tokens[i*bs:(i+1)*bs])`` —
radix-style: position and content are both in the chain) to the pool
block holding that span's k/v.  A new prompt's longest cached prefix
resolves to existing blocks with zero recompute; a block whose last
reference drops moves to a CACHED LRU list instead of the free list,
where it stays matchable until the allocator reclaims it under
pressure.  Eviction only ever takes refcount-0 cached blocks, so the
whole-reservation admission guarantee survives: blocks owned by an
admitted sequence are untouchable until that sequence frees them.

What a kind of layer keeps here, under which names and addressed by
what, is one table: serve/kinds.py.  Two kinds of block exist: those of a
sequence's table, reserved whole at admission, and those of the window
layers' table (:class:`WindowBlocks`) — a sequence holds only the blocks
its window still reaches, takes them as it grows and gives them back as
the window passes, so a 32k-token sequence costs those layers
``window_blocks_bound`` blocks (10 at a window of 513, chunks of 512 and
blocks of 128; 6 at a window of 128) where a full table would hold 260.

The allocator is deliberately host-side and synchronous: allocation
decisions happen at admission time (serve/engine.py), outside the
jitted hot path, exactly like the trainer's host/device split
(train/trainer.py dispatch vs resolution).
"""

from __future__ import annotations

import collections
import hashlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from torchacc_tpu.utils.metrics import counters


def blocks_needed(num_tokens: int, block_size: int) -> int:
    """Blocks required to hold ``num_tokens`` cache slots."""
    return -(-max(num_tokens, 0) // block_size)


class PrefixIndex:
    """Token-hash prefix index over pool blocks (radix-style chain).

    Each FULL block of a prompt gets a chain key: the blake2b digest of
    the parent block's key concatenated with this block's token ids.
    Chaining makes the key encode the block's absolute position AND the
    entire token prefix before it, so two entries collide only when the
    whole prefix up to and including the block is token-identical —
    exactly the condition under which the banked k/v is reusable
    (deterministic forward, same weights; serve/engine.load_params
    flushes the index on weight swaps).  16-byte digests make an
    accidental collision astronomically unlikely (~2^-128); there is no
    token-level compare on hit, which is the standard vLLM trade.

    The index never owns pool headroom: entries point at blocks that
    are either ALLOCATED (refcount >= 1, some live sequence reads them)
    or CACHED (refcount 0, parked in the pool's LRU).  ``forget`` is
    called by the pool when it evicts a cached block.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self._by_key: Dict[bytes, int] = {}
        self._key_of: Dict[int, bytes] = {}

    def __len__(self) -> int:
        return len(self._by_key)

    def keys(self, prompt: np.ndarray) -> List[bytes]:
        """Chain keys for every FULL block of ``prompt`` (a prompt
        shorter than one block has no keyable span)."""
        bs = self.block_size
        toks = np.ascontiguousarray(np.asarray(prompt, np.int32))
        out: List[bytes] = []
        parent = b""
        for i in range(int(toks.shape[0]) // bs):
            h = hashlib.blake2b(parent, digest_size=16)
            h.update(toks[i * bs:(i + 1) * bs].tobytes())
            parent = h.digest()
            out.append(parent)
        return out

    def match(self, keys: List[bytes]) -> List[int]:
        """The longest resident chain: blocks for keys[0..m) where every
        key hits.  Stops at the first miss — a surviving child whose
        parent was evicted is unreachable (and will age out of the LRU)
        but never wrongly matched."""
        blocks: List[int] = []
        for k in keys:
            b = self._by_key.get(k)
            if b is None:
                break
            blocks.append(b)
        return blocks

    def register(self, key: bytes, block: int) -> bool:
        """Map ``key`` -> ``block``; no-op (False) when the key is
        already mapped (first writer wins — concurrent identical
        prompts keep the earlier block, the later one stays private)
        or the block already carries a key."""
        if key in self._by_key or block in self._key_of:
            return False
        self._by_key[key] = block
        self._key_of[block] = key
        return True

    def owns(self, block: int) -> bool:
        return block in self._key_of

    def forget(self, block: int) -> None:
        k = self._key_of.pop(block, None)
        if k is not None:
            del self._by_key[k]

    def clear(self) -> int:
        """Drop every entry (weight swap / flush); returns the count."""
        n = len(self._by_key)
        self._by_key.clear()
        self._key_of.clear()
        return n


class BlockPool:
    """Refcounted free-list allocator over pool blocks 1..num_blocks-1.

    A block is in exactly one of three states:

    - FREE: on the free list, content garbage;
    - ALLOCATED: refcount >= 1 — handed to one ``alloc`` caller and
      possibly shared into other sequences' tables via :meth:`share`;
    - CACHED: refcount 0 but still holding reusable prefix k/v
      (``index.owns`` it), parked in an LRU from which :meth:`alloc`
      evicts oldest-first when the free list runs dry.

    Invariants (tested in tests/test_serving.py + test_prefix_cache.py):
    - block 0 (the null block) is never handed out;
    - ``free`` of a block with no outstanding reference raises
      (double-free / foreign-block detection — releasing a SHARED block
      once per sharer is legal, once more raises);
    - eviction only ever takes refcount-0 cached blocks, so an admitted
      sequence's reservation can never be reclaimed under it;
    - ``available + in_use == num_blocks - 1`` always (no leak;
      ``available`` counts free + cached since both are allocatable).
    """

    def __init__(self, num_blocks: int, index: Optional[PrefixIndex] = None):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (block 0 is reserved), got {num_blocks}")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        self._cached: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._index = index
        self.evictions = 0

    @property
    def available(self) -> int:
        """Blocks an ``alloc`` could grant: free + evictable cached."""
        return len(self._free) + len(self._cached)

    @property
    def in_use(self) -> int:
        return len(self._ref)

    @property
    def cached(self) -> int:
        return len(self._cached)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= self.available

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` blocks, or None when the pool lacks headroom (the
        admission-control signal — never a partial grant).  Evicts
        cached refcount-0 blocks oldest-first when the free list alone
        cannot cover the grant."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > self.available:
            return None
        while len(self._free) < n:
            b, _ = self._cached.popitem(last=False)      # LRU: oldest out
            if self._index is not None:
                self._index.forget(b)
            self.evictions += 1
            counters.inc("prefix_evictions")
            self._free.append(b)
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def share(self, block: int) -> None:
        """Take one more reference on an allocated block, or revive a
        cached one (prefix hit) — the block leaves the LRU and cannot
        be evicted until every reference drops."""
        if block in self._ref:
            self._ref[block] += 1
        elif block in self._cached:
            del self._cached[block]
            self._ref[block] = 1
        else:
            raise ValueError(
                f"share of block {block} which is neither allocated nor "
                f"cached (stale prefix-index entry, or a block this pool "
                f"never handed out)")

    def free(self, blocks: List[int]) -> None:
        """Release one reference per listed block.  The LAST release
        parks a prefix-indexed block in the cached LRU (most-recent
        end) instead of the free list, keeping its k/v matchable."""
        for b in blocks:
            r = self._ref.get(b)
            if r is None:
                raise ValueError(
                    f"free of block {b} which is not allocated (double "
                    f"free, or a block this pool never handed out)")
            if r > 1:
                self._ref[b] = r - 1
                continue
            del self._ref[b]
            if self._index is not None and self._index.owns(b):
                self._cached[b] = None
            else:
                self._free.append(b)

    def flush_cached(self) -> int:
        """Drop every cached refcount-0 block (and its index entries) —
        the weight-swap flush: banked k/v under old weights must never
        match a prompt served under new ones.  Blocks still referenced
        by live sequences are untouched (the caller guarantees there
        are none — serve/engine.load_params requires an idle engine)."""
        n = len(self._cached)
        while self._cached:
            b, _ = self._cached.popitem(last=False)
            if self._index is not None:
                self._index.forget(b)
            self._free.append(b)
        if self._index is not None:
            self._index.clear()
        return n


def window_blocks_bound(window: int, chunk: int, block_size: int) -> int:
    """The most window-layer blocks one sequence holds: a program's
    queries span at most ``chunk`` positions and each sees ``window``
    positions back and itself, so the live rows span ``window + 1 +
    chunk`` positions, which touch one block more than they fill."""
    return blocks_needed(window + 1 + chunk, block_size) + 1


class WindowBlocks:
    """The window layers' blocks: a :class:`BlockPool` of their own kind
    in which a sequence holds a block only while some query still to
    come can see a row of it.

    ``reserve`` at admission sets ``bound`` blocks aside for the
    sequence (never more are live, :func:`window_blocks_bound`), so that
    ``advance`` cannot fail; ``advance`` runs before every program of
    the sequence: it frees the blocks that lie wholly before the first
    query's window and takes the blocks the program's rows land in,
    writing both into the sequence's table row (0 = not held).  A freed
    block goes back to the pool at once: programs run in dispatch order
    on the device, so whoever is handed it next writes it after every
    program that could still read it has read it."""

    def __init__(self, num_blocks: int, block_size: int, window: int,
                 bound: int):
        self.pool = BlockPool(num_blocks)
        self.block_size, self.window, self.bound = block_size, window, bound
        self.reserved = 0
        self.freed = 0                      # blocks returned as windows passed

    def can_reserve(self) -> bool:
        return self.pool.num_blocks - 1 - self.reserved >= self.bound

    def reserve(self) -> None:
        if not self.can_reserve():
            raise ValueError("window blocks: reservation beyond the pool")
        self.reserved += self.bound

    def advance(self, held: Dict[int, int], row: np.ndarray,
                first_query: int, upto: int) -> bool:
        """Make ``held`` (logical block -> pool block) and ``row`` right
        for a program whose queries are positions [first_query, upto).
        Returns whether the row changed."""
        bs = self.block_size
        lo = max(first_query - self.window, 0) // bs
        hi = (upto - 1) // bs
        dead = [j for j in held if j < lo]
        for j in dead:
            self.pool.free([held.pop(j)])
            row[j] = 0
        self.freed += len(dead)
        new = [j for j in range(max(lo, max(held, default=-1) + 1), hi + 1)]
        if new:
            got = self.pool.alloc(len(new))
            if got is None or len(held) + len(new) > self.bound:
                raise RuntimeError(
                    f"window blocks: a sequence needs {len(held) + len(new)} "
                    f"blocks, its reservation is {self.bound}")
            for j, b in zip(new, got):
                held[j] = b
                row[j] = b
        return bool(dead or new)

    def release(self, blocks: List[int]) -> None:
        """A sequence is gone: its blocks and its reservation return."""
        self.pool.free(blocks)
        self.reserved -= self.bound


def latent_row_width(model_cfg) -> int:
    """Lanes of one token's row in a latent pool: ``[c_kv | rope(k_pe)]``
    (kv_lora_rank + qk_rope_head_dim values) padded to whole 128-lane
    tiles — a 576-lane row makes the compiler relayout the whole pool
    around the kernel (sandbox compile for v5e: a pool-sized temporary);
    a 640-lane one is read where it lies."""
    from torchacc_tpu.ops._common import round_up
    return round_up(model_cfg.kv_lora_rank + model_cfg.qk_rope_head_dim, 128)


def num_window_blocks(model_cfg, serve_cfg) -> int:
    """Blocks of the window layers' pool: every slot's bound and the
    null block.  No setting sizes it: a sequence's reservation is fixed
    at admission, so fewer blocks would only admit fewer sequences
    (``serve.max_slots`` says that) and more would never be touched."""
    return serve_cfg.max_slots * window_blocks_bound(
        model_cfg.window[0], serve_cfg.prefill_chunk,
        serve_cfg.block_size) + 1


def make_pools(model_cfg, serve_cfg, dtype=None):
    """The pools of a model BY NAME, in the model's compute dtype (the
    recurrent state always float32): for every kind of layer its plan
    holds (serve/kinds.kinds_of) the pools that kind's record owns,
    stacked over the layers of the kind, with as many rows as what
    addresses them has — ``serve.num_blocks`` blocks of the sequences'
    table, :func:`num_window_blocks` of the window layers' table, or
    ``serve.max_slots`` slots and the null slot.  When a mesh is live and
    its 'tp' divides the kv heads, the k/v rows are sharded over it in
    whole-head groups (the same activation-constraint seam the model
    layers use, so the TP head composes — parallel/sharding.py); a pool
    whose record names no axes is replicated."""
    from torchacc_tpu.parallel.sharding import activation_constraint
    from torchacc_tpu.serve.kinds import kinds_of

    rows = {"blocks": serve_cfg.num_blocks,
            "window": num_window_blocks(model_cfg, serve_cfg),
            "slot": serve_cfg.max_slots + 1}
    pools = {}
    for record, cfg, n in kinds_of(model_cfg).values():
        for name, (shape, dt, axes) in zip(record.names, record.shapes(
                cfg, n, rows[record.by], serve_cfg.block_size,
                dtype or model_cfg.dtype)):
            pools[name] = jnp.zeros(shape, dt)
            if axes is not None:
                pools[name] = activation_constraint(pools[name], axes)
    return pools
