"""Ragged-batching state: refcounted blocked KV allocator with a prefix
cache, sequence descriptors, state manager.

Port of the reference inference-v2 host-side design — the clean abstractions
SURVEY §7 says to keep: ``BlockedAllocator``
(inference/v2/ragged/blocked_allocator.py), ``DSSequenceDescriptor``
(sequence_descriptor.py), ``DSStateManager`` (ragged_manager.py:19) — grown
with vLLM-style prefix caching: blocks are refcounted, FULL blocks carry a
content key chained on their parent block, a new prompt reuses any cached
prefix run of matching blocks, and refcount-0 keyed blocks retire to an LRU
instead of the free list (evicted only when allocation demands it).  All
host-side Python; device state is the paged KV cache (paged.py) — the one
device interaction is the copy-on-write hook the engine installs so a
shared page is cloned before anyone writes into it.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple


def block_key(parent_block: Optional[int], tokens: Tuple[int, ...]):
    """Exact content key of one FULL KV block: the PARENT BLOCK's id (whose
    cached pages encode the entire preceding context) + this block's token
    window.  Identity-chained rather than hash-chained: dict lookup compares
    keys by full equality, so a FALSE prefix match is impossible — Python's
    64-bit tuple hash is collision-constructible, which is why vLLM moved
    its prefix-cache keys to sha256; chaining on the concrete parent block
    gets the same exactness with no digest.  The cost is that evicting a
    parent invalidates its cached descendants (their keys name a block id
    that may be reused for different content) — the allocator cascades
    eviction through ``_children`` for exactly that reason."""
    return (parent_block, tokens)


class BlockedAllocator:
    """Fixed pool of KV blocks managed as a refcounted free list plus an LRU
    of retired-but-cached blocks (reference: blocked_allocator.py int free
    list; the refcount/hash/LRU growth is the prefix-cache layer).

    Block lifecycle::

        free -> allocated (refcount 1) -> [shared: refcount k > 1]
             -> refcount 0 -> cached LRU (if it carries a content key,
                              pages intact, revivable by ``lookup``+``ref``)
                           -> free list (if unkeyed)
        cached LRU -> evicted (key dropped, descendants' keys cascade) when
                      ``allocate`` outruns the free list

    ``free_blocks`` counts only the free list; admission logic should use
    ``available_blocks`` (free + evictable cached).
    """

    def __init__(self, num_blocks: int, start: int = 0, stripes: int = 1):
        if num_blocks < 1:
            raise ValueError(f"need at least one block, got {num_blocks}")
        if stripes < 1 or num_blocks % stripes:
            raise ValueError(
                f"stripes ({stripes}) must be >= 1 and divide the pool "
                f"({num_blocks} blocks)")
        # ``start``: first GLOBAL block id this allocator owns.  Replica-
        # partitioned pools (2-D batch x model serve mesh) run one allocator
        # per contiguous range so block ids stay global — device block
        # tables and prefix-cache keys never need translation host-side.
        self._start = start
        self._num_blocks = num_blocks
        # ``stripes`` (3-D batch x seq x model serve mesh): the pool splits
        # into ``stripes`` CONTIGUOUS sub-ranges — stripe s owns global ids
        # [start + s*size, start + (s+1)*size) — mirroring the device pool's
        # seq-axis slices, and ``allocate(first_pos=...)`` round-robins a
        # sequence's chain over them so chain position i's page provably
        # lives on seq shard i % stripes (balanced per-hop ring work, and a
        # long sequence fits iff the AGGREGATE pool fits it).
        self._stripes = stripes
        self._stripe_size = num_blocks // stripes
        self._free: List[List[int]] = [
            list(range(start + s * self._stripe_size,
                       start + (s + 1) * self._stripe_size))
            for s in range(stripes)
        ]
        # indexed by (block - start): ids stay global, storage stays local
        self._refs: List[int] = [0] * num_blocks
        self._key_of: Dict[int, object] = {}  # block -> content key
        self._by_key: Dict[object, int] = {}  # content key -> block
        self._parent_of: Dict[int, int] = {}  # keyed block -> parent block
        self._children: Dict[int, set] = {}  # parent block -> keyed children
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # refcount-0 cached
        self.evictions = 0
        self.registrations = 0  # successful register() calls (cache version)

    @property
    def free_blocks(self) -> int:
        return sum(len(f) for f in self._free)

    @property
    def stripes(self) -> int:
        return self._stripes

    def stripe_of(self, block: int) -> int:
        """Which stripe (seq shard) owns ``block``."""
        self._check(block)
        return (block - self._start) // self._stripe_size

    def _push_free(self, block: int) -> None:
        return self._free[
            (block - self._start) // self._stripe_size].append(block)

    @property
    def cached_blocks(self) -> int:
        return len(self._lru)

    @property
    def available_blocks(self) -> int:
        """Immediately allocatable: free lists + evictable cached blocks."""
        return self.free_blocks + len(self._lru)

    @property
    def total_blocks(self) -> int:
        return self._num_blocks

    def refcount(self, block: int) -> int:
        self._check(block)
        return self._refs[block - self._start]

    def _check(self, block: int) -> None:
        if not self._start <= block < self._start + self._num_blocks:
            raise ValueError(f"invalid block id {block}")

    def can_allocate(self, n: int, first_pos: int = 0, hold=()) -> bool:
        """Whether ``allocate(n, first_pos)`` would succeed.  Under striping
        aggregate headroom is NOT sufficient: run entry ``j`` must come from
        stripe ``(first_pos + j) % stripes`` specifically.  ``hold``: cached-
        LRU blocks an admission is about to revive (prefix-matched blocks at
        refcount 0) — charged as unavailable, since revival pulls them out
        of the evictable pool before the fresh allocation runs."""
        held = set(hold)
        if self._stripes == 1:
            return n <= self.available_blocks - len(held & self._lru.keys())
        need = [0] * self._stripes
        for j in range(n):
            need[(first_pos + j) % self._stripes] += 1
        lru_per = [0] * self._stripes
        for b in self._lru:
            if b not in held:
                lru_per[(b - self._start) // self._stripe_size] += 1
        return all(len(self._free[s]) + lru_per[s] >= need[s]
                   for s in range(self._stripes))

    def allocate(self, n: int, first_pos: int = 0) -> List[int]:
        """Hand out ``n`` fresh blocks.  ``first_pos``: the chain position
        of the run's first block — run entry ``j`` is drawn from stripe
        ``(first_pos + j) % stripes`` so a sequence's pages round-robin
        across the seq shards (the identity at ``stripes == 1``)."""
        if not self.can_allocate(n, first_pos):
            raise RuntimeError(
                f"cannot allocate {n} blocks ({self.available_blocks} available)"
            )
        out: List[int] = []
        for j in range(n):
            s = (first_pos + j) % self._stripes
            if self._free[s]:
                b = self._free[s].pop()  # LIFO: O(1), and recently-freed
            else:  # pages are the warmest
                b = self._evict_one(s)
            self._refs[b - self._start] = 1
            out.append(b)
        return out

    def _evict_one(self, stripe: Optional[int] = None) -> int:
        """Drop the least-recently-used cached block, cascading its key AND
        every cached descendant's key: a descendant's key names this block
        id as its parent, and once the id is reused for other content a
        lookup through it would serve wrong pages.  ``stripe``: restrict to
        the LRU-oldest block of that stripe (striped pools evict within the
        stripe the allocation run needs)."""
        if stripe is None or self._stripes == 1:
            b, _ = self._lru.popitem(last=False)
        else:
            b = next((x for x in self._lru
                      if (x - self._start) // self._stripe_size == stripe),
                     None)
            if b is None:
                raise RuntimeError(f"no evictable blocks in stripe {stripe}")
            del self._lru[b]
        self._drop_key(b)
        self.evictions += 1
        return b

    def _drop_key(self, root: int) -> None:
        stack = [root]
        while stack:
            x = stack.pop()
            key = self._key_of.pop(x, None)
            if key is not None and self._by_key.get(key) == x:
                del self._by_key[key]
            p = self._parent_of.pop(x, None)
            if p is not None:
                self._children.get(p, set()).discard(x)
            stack.extend(self._children.pop(x, ()))
            # a de-keyed refcount-0 descendant is dead cache: straight to
            # the free list (the root itself is the caller's to hand out)
            if x != root and self._refs[x - self._start] == 0 and x in self._lru:
                del self._lru[x]
                self._push_free(x)

    def ref(self, block: int) -> None:
        """Take a reference on an allocated or cached block."""
        self._check(block)
        if self._refs[block - self._start] == 0:
            if block not in self._lru:
                raise ValueError(f"cannot ref free block {block}")
            del self._lru[block]  # revive from the cache
        self._refs[block - self._start] += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per block; last reference retires the block to
        the cached LRU (keyed) or the free list (unkeyed)."""
        from collections import Counter

        counts = Counter(blocks)
        for b, n in counts.items():
            self._check(b)
            # count duplicates within THIS call too: validating all entries
            # before any decrement would let free([b, b]) at refcount 1
            # slip past and drive the refcount negative
            if self._refs[b - self._start] < n:
                raise ValueError(f"double free of block {b}")
        for b in blocks:
            self._refs[b - self._start] -= 1
            if self._refs[b - self._start] == 0:
                if b in self._key_of:
                    self._lru[b] = None
                    self._lru.move_to_end(b)
                else:
                    self._push_free(b)

    def register(self, block: int, key, parent: Optional[int] = None) -> bool:
        """Publish ``block`` as holding the content ``key`` (a FULL block),
        chained under ``parent`` for eviction cascading.  First writer wins:
        a duplicate key keeps the existing mapping."""
        self._check(block)
        if self._refs[block - self._start] <= 0:
            raise ValueError(f"cannot register unowned block {block}")
        if block in self._key_of or key in self._by_key:
            return False
        self._key_of[block] = key
        self._by_key[key] = block
        if parent is not None:
            self._parent_of[block] = parent
            self._children.setdefault(parent, set()).add(block)
        self.registrations += 1
        return True

    def key_of(self, block: int):
        """The published content key of ``block`` (None if unkeyed)."""
        return self._key_of.get(block)

    def invalidate(self, block: int) -> None:
        """Retract ``block``'s published content key (cascading every cached
        descendant, exactly like eviction) WITHOUT touching refcounts — the
        quarantine path for suspect content: a block whose pages may hold
        NaN KV must stop serving prefix-cache hits, but sequences already
        holding references keep them (they fail on their own logits)."""
        self._check(block)
        self._drop_key(block)
        if self._refs[block - self._start] == 0 and block in self._lru:
            # a de-keyed block is dead cache: straight to the free list
            # (audit forbids unkeyed blocks in the LRU)
            del self._lru[block]
            self._push_free(block)

    def lookup(self, key) -> Optional[int]:
        """Block currently holding content ``key`` (caller must ``ref`` it)."""
        return self._by_key.get(key)

    def audit(self) -> None:
        """Invariant check for tests: every block is in exactly one of
        {free, cached LRU, active (refcount > 0)} and the key maps agree."""
        owned = range(self._start, self._start + self._num_blocks)
        for s, fl in enumerate(self._free):
            for b in fl:
                assert (b - self._start) // self._stripe_size == s, (
                    f"block {b} on stripe {s}'s free list but owned by "
                    f"stripe {(b - self._start) // self._stripe_size}")
        free = {b for fl in self._free for b in fl}
        lru = set(self._lru)
        active = {b for b in owned if self._refs[b - self._start] > 0}
        assert not (free & lru), f"free/lru overlap: {free & lru}"
        assert not (free & active), f"free/active overlap: {free & active}"
        assert not (lru & active), f"lru/active overlap: {lru & active}"
        assert free | lru | active == set(owned), "leaked blocks"
        assert all(self._refs[b - self._start] == 0 for b in free | lru)
        for b, key in self._key_of.items():
            assert self._by_key.get(key) == b
        for key, b in self._by_key.items():
            assert self._key_of.get(b) == key
        assert set(self._lru) <= set(self._key_of), "unkeyed block in LRU"
        for p, kids in self._children.items():
            for c in kids:
                assert self._parent_of.get(c) == p and c in self._key_of


@dataclass
class SequenceDescriptor:
    """Tracked state of one generation request
    (reference: sequence_descriptor.py DSSequenceDescriptor)."""

    uid: int
    slot: int  # row in the engine's static batch tensors
    blocks: List[int] = field(default_factory=list)
    seen_tokens: int = 0  # tokens whose KV is already in the cache
    tokens: List[int] = field(default_factory=list)  # full token history
    done: bool = False
    preempted: bool = False  # released by preemption: its per-slot state is recomputed
    cached_tokens: int = 0  # prefix tokens served from the block cache
    hashes: List[object] = field(default_factory=list)  # chained full-block keys
    # speculative-decoding state (engine_v2 drives these): accept-rate EMA
    # feeds the per-sequence draft-length throttle; a throttled-to-0
    # sequence decodes plainly and re-probes after spec_cooldown ticks
    spec_draft_len: int = -1  # current draft cap; -1 = unset (engine max)
    spec_ema: float = 1.0  # accept-rate EMA (optimistic start)
    spec_cooldown: int = 0  # plain-decode ticks left before a re-probe
    spec_drafted: int = 0  # lifetime drafted tokens (stats)
    spec_accepted: int = 0  # lifetime accepted tokens (stats)
    # set by the engine when this sequence's forward produced non-finite
    # logits (finite_guard sentinel) — the scheduler converts it into a
    # typed FAILED terminal state; direct put()/step() callers read it here
    error: Optional[str] = None
    # tokens sampled for this sequence by programs that are enqueued and not
    # yet collected (a tick dispatched one ahead): their VALUES are on the
    # device only, their COUNT is known, and every position, page and length
    # cap follows the count.  0 wherever dispatch and fetch are back to back.
    pending: int = 0

    @property
    def cur_len(self) -> int:
        """The sequence's length once every enqueued program has been
        collected: what positions and page growth are planned from."""
        return len(self.tokens) + self.pending


@dataclass(frozen=True)
class WindowCompaction:
    """A block table that SHRINKS while its sequence lives: the positions of an
    aligned window of ``window`` keep one exact row each while the window is
    open and one summary row per ``chunk`` positions once it has closed.  With
    ``per`` = ``window / chunk`` rows a closed window (one page of them: the
    manager checks), a sequence whose newest written position is ``n - 1`` holds,
    in table order,

    - one page per closed window (``w = (n - 1) // window`` of them),
    - the OPEN window's summary page, filled chunk by chunk (column ``w``),
    - the open window's exact pages (from column ``w + 1`` on),

    and when position ``window (w + 1) - 1`` has been written the window closes:
    its summary page stays where it is and every exact page goes back to the
    pool (``StateManager.close_window``)."""

    window: int
    chunk: int

    def column(self, pos: int, block_size: int) -> int:
        """The table column of the exact page that holds position ``pos``."""
        return pos // self.window + 1 + pos % self.window // block_size

    def pages_for(self, n: int, block_size: int) -> int:
        """Pages the table holds while position ``n - 1`` is the newest written
        (its window still open)."""
        if n <= 0:
            return 0
        last = n - 1
        return self.column(last, block_size) + 1

    def peak_pages(self, n: int, block_size: int) -> int:
        """The most pages the table holds on the way to ``n`` written positions:
        at ``n``, or while the last window that filled was filling."""
        return max(self.pages_for(n, block_size),
                   self.pages_for(n // self.window * self.window, block_size))

    def rows_live(self, n):
        """Rows a sequence of ``n`` written positions attends next (a window
        that has just filled counts as closed): position ``p``'s exact row is
        row ``rows_live(p)`` of what its sequence attends while its window is
        open.  Plain arithmetic: ``n`` may be an array of positions."""
        return n // self.window * (self.window // self.chunk) + n % self.window


class _AllocatorGroupView:
    """Aggregate read view over the per-replica allocators of a partitioned
    pool (``StateManager(replicas > 1)``) — keeps every pre-existing
    ``mgr.allocator`` consumer (admission headroom, leak audits, cache-
    version stamps) working unchanged.  Mutations go through the owning
    replica's allocator (``StateManager._alloc_of``), never this view."""

    def __init__(self, allocators: List[BlockedAllocator]):
        self._allocators = allocators
        self._per = allocators[0].total_blocks

    def _of(self, block: int) -> BlockedAllocator:
        return self._allocators[block // self._per]

    @property
    def free_blocks(self) -> int:
        return sum(a.free_blocks for a in self._allocators)

    @property
    def cached_blocks(self) -> int:
        return sum(a.cached_blocks for a in self._allocators)

    @property
    def available_blocks(self) -> int:
        return sum(a.available_blocks for a in self._allocators)

    @property
    def total_blocks(self) -> int:
        return sum(a.total_blocks for a in self._allocators)

    @property
    def evictions(self) -> int:
        return sum(a.evictions for a in self._allocators)

    @property
    def registrations(self) -> int:
        return sum(a.registrations for a in self._allocators)

    def refcount(self, block: int) -> int:
        return self._of(block).refcount(block)

    def key_of(self, block: int):
        return self._of(block).key_of(block)

    @property
    def stripes(self) -> int:
        return self._allocators[0].stripes

    def stripe_of(self, block: int) -> int:
        return self._of(block).stripe_of(block)

    def audit(self) -> None:
        for a in self._allocators:
            a.audit()


class StateManager:
    """Owns the allocator + uid->descriptor map and the block arithmetic
    (reference: ragged_manager.py DSStateManager).

    With ``enable_prefix_caching`` the manager also drives the reuse layer:
    ``admit`` matches the prompt's leading FULL blocks against the
    allocator's hash table (refcount sharing, no KV recompute),
    ``update_hashes`` publishes blocks as they fill, and ``ensure_writable``
    copy-on-writes a shared block before a sequence writes into it
    (``cow_hook(src, dst)`` — installed by the engine — performs the device
    page copy).
    """

    def __init__(self, num_blocks: int, block_size: int, max_seqs: int,
                 enable_prefix_caching: bool = False, replicas: int = 1,
                 seq_shards: int = 1):
        # ``replicas`` (2-D batch x model serve mesh): slots AND blocks
        # partition into ``replicas`` contiguous groups — group r's slots
        # only ever hold blocks from group r's range, so the device pool
        # can shard its block dim over the batch axis and each mesh replica
        # resolves its rows' block ids inside its local pool slice.
        # ``seq_shards`` (3-D batch x seq x model): each replica's range
        # further stripes into ``seq_shards`` contiguous sub-ranges, and a
        # sequence's chain round-robins across them — replica r stripe s is
        # exactly linear mesh shard r*S + s of the device pool's block dim,
        # so the kernel-side global->local translation needs no host help.
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if num_blocks % replicas or max_seqs % replicas:
            raise ValueError(
                f"num_blocks ({num_blocks}) and max_seqs ({max_seqs}) must "
                f"both divide into {replicas} serve replicas"
            )
        if seq_shards < 1:
            raise ValueError(f"seq_shards must be >= 1, got {seq_shards}")
        if (num_blocks // replicas) % seq_shards:
            raise ValueError(
                f"each replica's pool ({num_blocks // replicas} blocks) "
                f"must divide into {seq_shards} seq shards"
            )
        self.block_size = block_size
        self.replicas = replicas
        self.seq_shards = seq_shards
        self._blocks_per = num_blocks // replicas
        self._slots_per = max_seqs // replicas
        self.allocators = [
            BlockedAllocator(self._blocks_per, start=r * self._blocks_per,
                             stripes=seq_shards)
            for r in range(replicas)
        ]
        # single-replica managers expose the one allocator object unchanged
        # (the overwhelmingly common case and every pre-existing caller);
        # replica-partitioned managers expose an aggregate read view
        self.allocator = (self.allocators[0] if replicas == 1
                          else _AllocatorGroupView(self.allocators))
        self.max_seqs = max_seqs
        self.seqs: Dict[int, SequenceDescriptor] = {}
        self._slot_groups = [
            list(range(r * self._slots_per, (r + 1) * self._slots_per))
            for r in range(replicas)
        ]
        self.enable_prefix_caching = enable_prefix_caching
        self.cow_hook: Optional[Callable[[int, int], None]] = None
        # ``release_hook(seq)``: the engine's per-SLOT state that is not blocks
        # (a sliding layer's ring) is let go with the sequence
        self.release_hook: Optional[Callable[[SequenceDescriptor], None]] = None
        # ``compaction`` (the engine's, from its runner): the tables of this
        # model give pages back while their sequences live, so a sequence of n
        # tokens holds ``compaction.pages_for(n)`` pages, not ``ceil(n / block)``
        self.compaction: Optional[WindowCompaction] = None
        # chaos-harness hook (inference/faults.py FaultInjector): when set,
        # ``ensure_capacity`` consults the ``alloc_exhaustion`` injection
        # point before touching the real pool — the scheduler's retry /
        # preemption paths then run against deterministic pressure
        self.faults = None
        self.prompt_tokens_total = 0
        self.cached_prompt_tokens = 0
        # per-replica splits of the two hit-rate counters above (replica r's
        # numbers only ever move with its own admissions/re-matches) — the
        # serve/replicaN/* telemetry reads these through ``replica_stats``
        self.prompt_tokens_by_replica = [0] * replicas
        self.cached_tokens_by_replica = [0] * replicas
        self.cow_copies = 0

    @property
    def free_slots(self) -> int:
        return sum(len(g) for g in self._slot_groups)

    def per_replica_token_budget(self, total: int) -> int:
        """Per-replica share of a shared token budget (the scheduler's
        prefill chunk, the engine's pack budget): ``total // replicas``
        floored to page alignment with a one-page minimum; the identity at
        ``replicas == 1``.  ONE implementation on purpose — scheduler
        chunks and engine packs must round identically or scheduler-sized
        chunks overflow engine per-replica chunks every tick."""
        if self.replicas == 1:
            return total
        bs = self.block_size
        return max(bs, (total // self.replicas) // bs * bs)

    def replica_of(self, seq: SequenceDescriptor) -> int:
        return seq.slot // self._slots_per

    def _alloc_of(self, seq: SequenceDescriptor) -> BlockedAllocator:
        return self.allocators[self.replica_of(seq)]

    def _walk_chain(self, tokens, allocator: BlockedAllocator):
        """THE content-chain walk: yield ``(key, block)`` for each cached
        FULL leading block of ``tokens``, chaining each key on the matched
        parent block, capped at ``(len - 1) // block_size`` (the final
        prompt token always recomputes — see ``_match_prefix``).  Single
        implementation by design: placement probes (``_probe_match``) and
        allocation (``_match_prefix``) both ride it, so the two can never
        desynchronize on the key scheme or the match cap."""
        bs = self.block_size
        parent: Optional[int] = None
        for i in range((len(tokens) - 1) // bs):
            key = block_key(parent, tuple(
                int(t) for t in tokens[i * bs:(i + 1) * bs]))
            b = allocator.lookup(key)
            if b is None:
                return
            yield key, b
            parent = b

    def _probe_match(self, tokens,
                     allocator: BlockedAllocator) -> Tuple[int, List[int]]:
        """Non-mutating probe over ``_walk_chain``: no references taken.
        Returns ``(matched_blocks, lru_blocks)`` where ``lru_blocks`` are
        the matched blocks currently parked refcount-0 in the cached LRU —
        admitting would revive them OUT of the available pool, so
        feasibility must charge them even though no fresh allocation
        happens.  Placement (``_pick_replica``) and the all-or-nothing
        simulation (``can_admit_all``) both ride on this; the winning
        replica's chain is re-walked once by ``_match_prefix`` at the real
        admit (O(matched) dict lookups — the scheduler's denied-state memo
        bounds repeat probes)."""
        matched = 0
        lru: List[int] = []
        for _key, b in self._walk_chain(tokens, allocator):
            matched += 1
            if allocator.refcount(b) == 0:
                lru.append(b)
        return matched, lru

    def pages_for(self, n_tokens: int) -> int:
        """Pages a sequence of ``n_tokens`` holds in its table."""
        if self.compaction is not None:
            return self.compaction.pages_for(n_tokens, self.block_size)
        return -(-n_tokens // self.block_size)

    def _pick_replica(self, prompt_len: int,
                      tokens=None) -> Optional[int]:
        """Admission placement, replica-AFFINE for content: among replica
        groups with a free slot that can fit the prompt, prefer the one
        already holding its DEEPEST cached prefix (ties and the no-match
        case fall back to most immediately-allocatable blocks — the
        historical headroom balancing).  Feasibility credits the matched
        run: only the fresh remainder needs allocating, plus the matched
        LRU blocks a revival pulls out of the available pool.  None when
        nobody fits — the scheduler's per-replica batch balancing and the
        prefix-affinity routing both ride on this single decision point."""
        blocks = self.pages_for(prompt_len)
        probe = self.enable_prefix_caching and tokens is not None
        best, best_key = None, None
        for r in range(self.replicas):
            if not self._slot_groups[r]:
                continue
            a = self.allocators[r]
            matched, lru = (self._probe_match(tokens, a) if probe
                            else (0, []))
            # striping-aware: fresh blocks land at chain positions
            # matched..blocks-1 and each must fit its owning stripe
            if not a.can_allocate(blocks - matched, first_pos=matched,
                                  hold=lru):
                continue
            key = (matched, a.available_blocks)
            if best_key is None or key > best_key:
                best, best_key = r, key
        return best

    def can_admit_all(self, prompt_lens, token_lists=None) -> bool:
        """Whether ALL prompts can be admitted together: a greedy simulation
        of the sequential per-replica placement ``admit`` performs
        (deepest-cached-prefix replica first, then most headroom, with a
        free slot that fits, in submission order).  Aggregate-pool
        arithmetic is NOT sufficient under replicas — a prompt can fit the
        sum of two half-empty pools while fitting neither — and the
        engine's all-or-nothing ``put()`` contract needs the answer BEFORE
        the first admission mutates anything.

        ``token_lists`` (same order as ``prompt_lens``) lets the simulation
        credit prefix-matched blocks exactly the way
        ``admit(match_prefix=True)`` will allocate: a matched run costs no
        fresh blocks, matched LRU blocks are charged ONCE (the first
        admission revives them; later sharers just take references).
        Without tokens the simulation stays conservative (full block
        count), which can spuriously reject admissible batches once the
        cache is warm.

        One un-modeled corner: the simulation probes every prompt against
        the CURRENT cache, but a real earlier admission in the same batch
        can evict LRU blocks a later prompt's credit assumed (the fresh
        allocation outran the free list), flipping that prompt's
        affinity placement and, in tight pools, its feasibility.  The
        per-replica block charge itself is tight (matched LRU blocks are
        a suffix of the matched run), but a True here is a strong
        prediction, not a reservation — which is why ``put()`` keeps its
        rollback path for pre-check defeats."""
        slots = [len(g) for g in self._slot_groups]
        avail = [a.available_blocks for a in self.allocators]
        probe = self.enable_prefix_caching and token_lists is not None
        revived: set = set()  # LRU blocks already charged this simulation
        for i, n in enumerate(prompt_lens):
            blocks = self.pages_for(int(n))
            toks = token_lists[i] if probe else None
            best, best_key, best_need, best_lru = -1, None, 0, ()
            for r in range(self.replicas):
                if not slots[r]:
                    continue
                matched, lru = (self._probe_match(toks, self.allocators[r])
                                if probe else (0, []))
                fresh_lru = [b for b in lru if b not in revived]
                need = (blocks - matched) + len(fresh_lru)
                # the aggregate running counter catches cross-admission
                # pressure; the per-stripe probe (against CURRENT state —
                # one more un-modeled corner of the kind the docstring
                # already concedes) catches a full stripe hiding behind
                # aggregate headroom
                if avail[r] < need or not self.allocators[r].can_allocate(
                        blocks - matched, first_pos=matched, hold=fresh_lru):
                    continue
                key = (matched, avail[r])
                if best_key is None or key > best_key:
                    best, best_key = r, key
                    best_need, best_lru = need, fresh_lru
            if best < 0:
                return False
            slots[best] -= 1
            avail[best] -= best_need
            revived.update(best_lru)
        return True

    def blocks_needed(self, seq: SequenceDescriptor, new_tokens: int) -> int:
        if self.compaction is not None:
            return max(0, self.pages_for(seq.cur_len + new_tokens) - len(seq.blocks))
        have = len(seq.blocks) * self.block_size
        need = seq.cur_len + new_tokens
        return max(0, -(-(need - have) // self.block_size))

    def can_admit(self, prompt_len: int, tokens=None) -> bool:
        return self._pick_replica(prompt_len, tokens) is not None

    def _match_prefix(
        self, tokens: List[int], allocator: Optional[BlockedAllocator] = None
    ) -> Tuple[List[int], List[object]]:
        """Longest cached run of FULL leading blocks for ``tokens``.  Capped
        at ``(len-1)//block_size`` blocks so at least the final prompt token
        is always recomputed (its logits are needed, and its KV write must
        land in a page this sequence owns — never a shared one).  The walk
        chains each key on the MATCHED parent block's id, so every hop is an
        exact-content match (see ``block_key``).  ``allocator``: the
        replica allocator to match in (default: replica 0 — the only one
        in the common single-replica case)."""
        if allocator is None:
            allocator = self.allocators[0]
        blocks: List[int] = []
        keys: List[object] = []
        for key, b in self._walk_chain(tokens, allocator):
            allocator.ref(b)
            blocks.append(b)
            keys.append(key)
        return blocks, keys

    def admit(self, uid: int, prompt_tokens: List[int],
              match_prefix: bool = True) -> SequenceDescriptor:
        """Track a new sequence.  ``match_prefix=False`` skips the prefix-
        cache walk even when caching is enabled — the KV-handoff adoption
        path (serving/handoff.py) needs exclusively-owned fresh pages to
        scatter a migrated sequence's extracted KV into; sharing a cached
        block there would stomp content other sequences are reading."""
        if uid in self.seqs:
            raise ValueError(f"uid {uid} already tracked")
        if self.free_slots == 0:
            raise RuntimeError("no free sequence slots")
        r = self._pick_replica(len(prompt_tokens),
                               prompt_tokens if match_prefix else None)
        if r is None:
            # keep the historical contract: slot exhaustion raises here,
            # block shortfall surfaces from allocate() below — pick any
            # replica with a free slot and let its allocator raise
            r = max((x for x in range(self.replicas) if self._slot_groups[x]),
                    key=lambda x: self.allocators[x].available_blocks)
        seq = SequenceDescriptor(uid=uid, slot=self._slot_groups[r].pop(0))
        seq.tokens = list(prompt_tokens)
        if self.enable_prefix_caching and match_prefix:
            seq.blocks, seq.hashes = self._match_prefix(
                seq.tokens, self.allocators[r])
            seq.cached_tokens = len(seq.blocks) * self.block_size
            seq.seen_tokens = seq.cached_tokens
            self.cached_prompt_tokens += seq.cached_tokens
            self.cached_tokens_by_replica[r] += seq.cached_tokens
        self.prompt_tokens_total += len(seq.tokens)
        self.prompt_tokens_by_replica[r] += len(seq.tokens)
        self.seqs[uid] = seq
        return seq

    def ensure_capacity(self, seq: SequenceDescriptor, new_tokens: int) -> None:
        n = self.blocks_needed(seq, new_tokens)
        if n:
            if self.faults is not None:
                # only growth consults the injector: a no-growth call must
                # stay infallible (retry loops rely on it converging)
                self.faults.maybe_raise("alloc_exhaustion", uids=(seq.uid,))
            seq.blocks.extend(self._alloc_of(seq).allocate(
                n, first_pos=len(seq.blocks)))

    def ensure_pages(self, seq: SequenceDescriptor, n_tokens: int) -> None:
        """Grow ``seq``'s table to what it holds once ``n_tokens`` positions are
        written (a prompt's chunk that ends there): under a ``compaction`` the
        prompt's END holds fewer pages than a window in the filling, so
        admission's reserve is not the chunks'."""
        n = self.pages_for(n_tokens) - len(seq.blocks)
        if n > 0:
            if self.faults is not None:
                self.faults.maybe_raise("alloc_exhaustion", uids=(seq.uid,))
            seq.blocks.extend(self._alloc_of(seq).allocate(n, first_pos=len(seq.blocks)))

    def close_window(self, seq: SequenceDescriptor, n_written: int) -> int:
        """``seq`` has just had position ``n_written - 1`` written, the last of
        its window: the window's exact pages leave the table and go BACK to the
        pool while the sequence lives on (its summary page stays, now a closed
        window's).  Call it once the execution that wrote the position is
        ENQUEUED: that execution is the last to read the pages, and whatever is
        handed them next is enqueued after it.  Returns the pages given back."""
        c = self.compaction
        if c is None or n_written <= 0 or n_written % c.window:
            raise ValueError(f"no window closes at {n_written} written positions")
        # the columns after the closed windows' pages (this window's summary page
        # the last of them); pages reserved past the window's own move down
        first, own = n_written // c.window, c.window // self.block_size
        gone = seq.blocks[first:first + own]
        del seq.blocks[first:first + own]
        if gone:
            self._alloc_of(seq).free(gone)
        return len(gone)

    def ensure_writable(self, seq: SequenceDescriptor, pos: int) -> None:
        """Copy-on-write guard: the page holding token position ``pos`` must
        be exclusively owned before it is written.  In the block-granular
        sharing scheme only FULL blocks are ever shared, so writes normally
        land in unshared pages — this is the safety net that keeps that an
        invariant rather than an assumption."""
        i = pos // self.block_size
        if i >= len(seq.blocks):
            return
        alloc = self._alloc_of(seq)
        b = seq.blocks[i]
        if alloc.refcount(b) <= 1:
            return
        [new] = alloc.allocate(1, first_pos=i)  # stay in position i's stripe
        if self.cow_hook is not None:
            self.cow_hook(b, new)
        alloc.free([b])
        seq.blocks[i] = new
        del seq.hashes[i:]  # content diverges from the published chain here
        self.cow_copies += 1

    def truncate_to_length(self, seq: SequenceDescriptor,
                           n_tokens: Optional[int] = None) -> int:
        """Free the block tail beyond what ``n_tokens`` (default: the
        sequence's current length) needs — the speculative-rollback path.

        A verify pass reserves pages for the full draft (``ensure_capacity``
        over k+1 tokens); when most drafts are rejected those tail slots
        would otherwise stay allocated until the sequence grew into them,
        silently shrinking the pool every speculating sequence by up to
        ``ceil(k/block_size)`` blocks.  Freeing goes through the allocator's
        normal deref (``free``), so a tail block that happens to be shared
        or prefix-cached just drops one reference — cached-LRU membership,
        other sequences' refcounts, and the published hash chains of KEPT
        blocks are untouched.  The sequence's own hash list is clipped to
        the kept range (it never extends past committed full blocks, so
        this is a no-op outside defensive cases).  Returns blocks freed.
        """
        if n_tokens is None:
            n_tokens = seq.cur_len
        keep = -(-n_tokens // self.block_size)
        if len(seq.blocks) <= keep:
            return 0
        tail = seq.blocks[keep:]
        del seq.blocks[keep:]
        del seq.hashes[keep:]
        self._alloc_of(seq).free(tail)
        return len(tail)

    def extend_match(self, seq: SequenceDescriptor) -> None:
        """Late re-match: blocks published AFTER this sequence was admitted
        (typically by the cold request ahead of it in the same arrival
        burst) replace its corresponding still-unwritten fresh pages.  Only
        runs while the hash chain is flush with prefill progress, so every
        replaced page is provably unwritten; the recompute cap of
        ``_match_prefix`` applies unchanged."""
        if not self.enable_prefix_caching:
            return
        alloc = self._alloc_of(seq)
        bs = self.block_size
        cap = (len(seq.tokens) - 1) // bs
        while seq.seen_tokens == len(seq.hashes) * bs:
            i = len(seq.hashes)
            if i >= cap or i >= len(seq.blocks):
                break
            parent = seq.blocks[i - 1] if i else None
            key = block_key(parent, tuple(seq.tokens[i * bs:(i + 1) * bs]))
            b = alloc.lookup(key)
            if b is None:
                break
            old = seq.blocks[i]
            alloc.ref(b)
            seq.blocks[i] = b
            alloc.free([old])
            seq.hashes.append(key)
            seq.seen_tokens = (i + 1) * bs
            seq.cached_tokens = seq.seen_tokens
            self.cached_prompt_tokens += bs
            self.cached_tokens_by_replica[self.replica_of(seq)] += bs

    def update_hashes(self, seq: SequenceDescriptor) -> None:
        """Publish every newly-FULL block of ``seq`` (prompt and generated
        alike — generated pages make preemption-by-recompute cheap).  Only
        tokens whose KV is actually written (``seen_tokens``) count."""
        if not self.enable_prefix_caching:
            return
        alloc = self._alloc_of(seq)
        bs = self.block_size
        full = min(seq.seen_tokens, len(seq.blocks) * bs) // bs
        while len(seq.hashes) < full:
            i = len(seq.hashes)
            parent = seq.blocks[i - 1] if i else None
            key = block_key(parent, tuple(seq.tokens[i * bs:(i + 1) * bs]))
            seq.hashes.append(key)
            # register only canonical chains: if the parent block lost (or
            # never won) its key, a child key naming it would dangle once
            # the parent id is reused — unreachable at best, wrong at worst
            if parent is None or alloc.key_of(parent) is not None:
                alloc.register(seq.blocks[i], key, parent=parent)

    def quarantine_written(self, seq: SequenceDescriptor) -> None:
        """Retract the prefix-cache keys of every block SEQ ITSELF wrote and
        published (its hash chain past the admission-matched prefix) — the
        engine calls this when the sequence's forward produced non-finite
        logits, since KV written by that forward (including earlier chunks
        of the same prompt) is suspect.  Blocks matched FROM the cache were
        written by healthy requests and keep their keys; so do duplicate
        keys whose canonical holder is another request's block."""
        if not self.enable_prefix_caching:
            return
        alloc = self._alloc_of(seq)
        first_own = seq.cached_tokens // self.block_size
        for i in range(first_own, min(len(seq.hashes), len(seq.blocks))):
            b = seq.blocks[i]
            if alloc.key_of(b) == seq.hashes[i]:
                alloc.invalidate(b)

    def hit_stats_snapshot(self) -> tuple:
        """The hit-rate counter state (aggregate + per-replica splits) as
        one opaque value — probe paths (tentative admits, adoption) save it
        before ``admit`` and hand it back to :meth:`hit_stats_restore` on
        rollback so the prefix-hit telemetry never counts a request twice
        or counts one that was never really admitted."""
        return (self.prompt_tokens_total, self.cached_prompt_tokens,
                tuple(self.prompt_tokens_by_replica),
                tuple(self.cached_tokens_by_replica))

    def hit_stats_restore(self, snap: tuple) -> None:
        self.prompt_tokens_total, self.cached_prompt_tokens = snap[0], snap[1]
        self.prompt_tokens_by_replica = list(snap[2])
        self.cached_tokens_by_replica = list(snap[3])

    def replica_stats(self) -> List[Dict[str, float]]:
        """Per-replica serving-health rows (one dict per replica): pool
        occupancy and the prefix-hit split — the host-side source for the
        ``serve/replicaN/*`` gauges."""
        out: List[Dict[str, float]] = []
        for r, a in enumerate(self.allocators):
            pt = self.prompt_tokens_by_replica[r]
            ct = self.cached_tokens_by_replica[r]
            out.append(dict(
                free_blocks=a.free_blocks,
                cached_blocks=a.cached_blocks,
                available_blocks=a.available_blocks,
                total_blocks=a.total_blocks,
                free_slots=len(self._slot_groups[r]),
                prompt_tokens=pt,
                cached_prompt_tokens=ct,
                prefix_hit_rate=(ct / pt if pt else 0.0),
                headroom=a.available_blocks / a.total_blocks,
            ))
        return out

    def release(self, uid: int) -> None:
        seq = self.seqs.pop(uid)
        if seq.blocks:
            self._alloc_of(seq).free(seq.blocks)
        if self.release_hook is not None:
            self.release_hook(seq)
        self._slot_groups[self.replica_of(seq)].append(seq.slot)

    @property
    def active(self) -> List[SequenceDescriptor]:
        return sorted(self.seqs.values(), key=lambda s: s.slot)
