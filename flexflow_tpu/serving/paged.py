"""Paged KV-cache management: block pool, page tables, COW prefix reuse.

The host-side policy half of the paged serving layout (the device half is
ops/inc_attention.py's paged op + kernels/flash_attention.py's paged
decode kernel). vLLM/PagedAttention (SOSP '23, PAPERS.md) is the
grounding: KV rows live in fixed-size BLOCKS drawn from one shared pool;
each slot owns a PAGE TABLE mapping its logical block index to a physical
block. Three consequences this module implements:

- **allocation at block granularity** — a slot holds ceil(length/bs)
  blocks, not max_seq rows, so short generations stop paying long-context
  HBM and the pool (not slots × max_seq) bounds concurrency;
- **prefix sharing via a radix tree** (radix.RadixPrefixCache) — prompt
  blocks are published into a token-labelled radix tree at prefill
  completion, keyed on the PROMPT extent only (K/V of a row depends on
  every token before it, so tree position is the content address); a new
  request maps the longest cached extent — including a partial match
  inside one block — into its own table (refcount++) and skips
  recomputing it. Each cached node holds one refcount on its block (the
  CACHE PIN), so prefixes SURVIVE their residents: sharing is
  cross-time, not just among live slots;
- **copy-on-write** — a write (decode append, or a prompt tail diverging
  inside a shared block) targeting a block with more than one reference
  first copies it to a fresh block (`CopyPlan` — the engine runs the
  device-side block copy). The pin makes every cached block
  COW-protected: a decode extending past its prompt can never overwrite
  cached prompt content (the poisoning the old full-prefix registry
  allowed), it pays one copy and owns the fresh block.

Physical block 0 is the RESERVED SCRATCH BLOCK (never allocated, never
freed): unallocated page-table entries point at it, and the device op
routes position-clipped writes there — the paged equivalent of the
contiguous layout's scratch row.

Pool pressure: admission reserves each request's worst case against the
FREE list (Σ reservations <= free blocks at all times, so a decode write
can NEVER exhaust the pool mid-flight); when the free list is too small,
`reserve` first EVICTS cold cache leaves LRU-first (radix.evict_lru) —
an evicted node only frees its block when the pin was the last
reference; a block a live slot still maps merely leaves the cache.
`cross_time=False` reproduces the old live-residents-only sharing (the
pin is dropped as the last holder releases) — the bench ablation.

**Groups.** Layers that attend a window of their past (ops/attention.
AttentionFrontEnd.window) keep their rows in a pool of their own, the
WINDOW GROUP (`WindowGroup`): its own size, free list and references, its
own page table a slot (the same logical indexing: block j holds rows
[j * block_size, (j + 1) * block_size)), and a slot holds there only the
blocks its next rows can still read: those from the block of row
`position - window + 1` on. What falls behind is unmapped as the slot
advances, by a decode step and by a chunk step alike, and its table entry
becomes the scratch block (the device op never reads behind the window).
The GLOBAL GROUP is what this module was before there were groups. The
radix cache is one: a cached node's block in the global group may have a
window block pinned beside it (`BlockManager._wpins`), and a cached extent
is usable up to a length only where the window group holds the blocks of
the `window - 1` rows before that length: a continuation reads them, and
nothing short of the whole forward over the prefix brings them back. A
copy-on-write of a shared tail block copies it in both groups
(`CopyPlan.group`). The window group reserves a slot's share at admission
(`WindowGroup.slot_blocks`: the window and a step's rows, not prompt +
new), and under pressure gives up the window blocks of the least recently
used cached nodes, which shortens what can be matched there.

Pure host code (no jax): unit-testable without a mesh.
"""

from __future__ import annotations

from dataclasses import dataclass

from .radix import RadixPrefixCache

SCRATCH_BLOCK = 0


@dataclass
class CopyPlan:
    """One COW copy the engine must run on the pool state BEFORE the next
    device step writes: physical block `src` duplicated into `dst`, in the
    pools of `group` (0: the global group's layers, 1: the window
    group's)."""

    src: int
    dst: int
    group: int = 0


@dataclass
class PagedStats:
    prefix_queries: int = 0        # admissions that attempted a match
    prefix_hits: int = 0           # admissions that shared >= 1 token
    shared_tokens: int = 0         # prompt tokens served from shared blocks
    prompt_tokens: int = 0         # total prompt tokens admitted
    cow_copies: int = 0
    blocks_in_use_peak: int = 0    # peak LIVE blocks (cache-only excluded)
    cross_time_hits: int = 0       # hits where a matched block had no
    #                                live holder — served from the cache
    #                                after its residents exited
    radix_evictions: int = 0       # nodes evicted (LRU or pin-drop)
    radix_evicted_blocks: int = 0  # blocks actually freed by eviction
    # the window group (0 where the graph has none): peak blocks its live
    # slots held, blocks unmapped because they fell behind a slot's
    # window, copy-on-write copies there, cached nodes whose window block
    # was given up under pressure
    window_blocks_in_use_peak: int = 0
    window_blocks_freed: int = 0
    window_cow_copies: int = 0
    window_pins_dropped: int = 0

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens whose K/V came from a shared
        block instead of being recomputed and re-stored."""
        if self.prompt_tokens == 0:
            return 0.0
        return self.shared_tokens / self.prompt_tokens


def window_slot_blocks(window: int, span: int, block_size: int,
                       aligned: bool = False) -> int:
    """Blocks of the window group a slot may hold at once: those of the
    window before a step's first row and of its `span` rows, one more
    where a block boundary falls inside, one for a copy-on-write in
    flight. Under an `aligned` window: the whole window of a step's first
    row, the blocks its `span` rows may take of the next one (their first
    block begins with that window: no boundary falls inside), one for a
    copy-on-write in flight."""
    if aligned:
        return -(-window // block_size) + -(-max(1, span) // block_size) + 1
    return -(-(window - 1 + max(1, span)) // block_size) + 2


class WindowGroup:
    """The pool of the layers that attend a window (module docstring):
    free list, references (live mappings + cache pins) and a table a slot.
    Policy that needs the radix cache (which cached nodes hold a window
    block, whom to take one from) is the BlockManager's, which owns this
    as `window`."""

    def __init__(self, num_blocks: int, block_size: int, window: int,
                 span: int, make_room, aligned: bool = False):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 window blocks (scratch + 1), got {num_blocks}")
        if aligned and window % block_size:
            raise ValueError(
                f"an aligned window of {window} rows needs blocks that "
                f"divide it, got block_size {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.window = int(window)
        # aligned: a row at t reads from window * (t // window) on (the
        # layers declare it, DecodeState.window_aligned); else its nearest
        # `window` rows
        self.aligned = bool(aligned)
        self.slot_blocks = window_slot_blocks(self.window, int(span),
                                              self.block_size, self.aligned)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._refcount: dict[int, int] = {}   # live mappings + cache pins
        self._mapped: dict[int, int] = {}     # live mappings alone
        self._pinned: set[int] = set()
        self._tables: dict[int, list[int]] = {}
        self._reserved: dict = {}
        self._make_room = make_room

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        """Blocks at least one live slot maps."""
        return len(self._mapped)

    @property
    def blocks_held(self) -> int:
        """Blocks a live slot maps or the cache pins."""
        return len(self._refcount)

    def first_row(self, position: int) -> int:
        """The first row a row at `position` reads."""
        if self.aligned:
            return position // self.window * self.window
        return max(position - self.window + 1, 0)

    def first_block(self, position: int) -> int:
        """The first logical block a row at `position` reads."""
        return self.first_row(position) // self.block_size

    def table(self, slot: int, width: int) -> list[int]:
        t = self._tables.get(slot, [])
        return t + [SCRATCH_BLOCK] * (width - len(t))

    # reservations: a slot's share, whatever its prompt
    def reserve(self, key) -> bool:
        if ((len(self._reserved) + 1) * self.slot_blocks
                > self.num_blocks - 1):
            return False
        self._reserved[key] = self.slot_blocks
        return True

    def bind_slot(self, key, slot: int):
        """The reservation made under `key` is `slot`'s from now on."""
        if key in self._reserved:
            self._reserved[slot] = self._reserved.pop(key)

    def _take(self, block: int):
        self._refcount[block] = self._refcount.get(block, 0) + 1

    def _drop(self, block: int):
        n = self._refcount[block] - 1
        if n == 0:
            del self._refcount[block]
            self._free.append(block)
        else:
            self._refcount[block] = n

    def _map(self, block: int):
        """One more live slot maps `block`."""
        self._mapped[block] = self._mapped.get(block, 0) + 1
        self._take(block)

    def _unmap(self, block: int):
        n = self._mapped[block] - 1
        if n:
            self._mapped[block] = n
        else:
            del self._mapped[block]
        self._drop(block)

    def pin(self, block: int):
        self._pinned.add(block)
        self._take(block)

    def unpin(self, block: int):
        self._pinned.discard(block)
        self._drop(block)

    def _alloc(self) -> int:
        if not self._free:
            self._make_room()
        if not self._free:
            raise RuntimeError(
                "window-group KV pool exhausted: the admission reservations "
                "(WindowGroup.reserve) must prevent this")
        blk = self._free.pop()
        self._map(blk)
        return blk

    def admit(self, slot: int, blocks: dict):
        """Map `blocks` {logical block: cached window block} into the
        slot's new table."""
        table = [SCRATCH_BLOCK] * (max(blocks) + 1 if blocks else 0)
        for lb, blk in blocks.items():
            table[lb] = blk
            self._map(blk)
        self._tables[slot] = table

    def ensure_writable(self, slot: int, positions, stats) -> list:
        """As BlockManager.ensure_writable, for this group: first unmap
        what the step's first row no longer reads, then own every block
        the step writes."""
        table = self._tables[slot]
        bs = self.block_size
        lo, hi = min(positions), max(positions)
        for lb in range(min(self.first_block(lo), len(table))):
            if table[lb] != SCRATCH_BLOCK:
                self._unmap(table[lb])
                table[lb] = SCRATCH_BLOCK
                stats.window_blocks_freed += 1
        copies = []
        for lb in range(lo // bs, hi // bs + 1):
            while len(table) <= lb:
                table.append(SCRATCH_BLOCK)
            blk = table[lb]
            if blk == SCRATCH_BLOCK:
                table[lb] = self._alloc()
            elif self._refcount[blk] > 1:
                fresh = self._alloc()
                self._unmap(blk)
                table[lb] = fresh
                copies.append(CopyPlan(src=blk, dst=fresh, group=1))
                stats.window_cow_copies += 1
        stats.window_blocks_in_use_peak = max(
            stats.window_blocks_in_use_peak, self.blocks_in_use)
        return copies

    def release(self, slot: int):
        self._reserved.pop(slot, None)
        for blk in self._tables.pop(slot, []):
            if blk != SCRATCH_BLOCK:
                self._unmap(blk)

    def check_invariants(self):
        free = set(self._free)
        assert SCRATCH_BLOCK not in free | set(self._refcount)
        assert not (free & set(self._refcount)), "block both free and held"
        assert len(free) + len(self._refcount) == self.num_blocks - 1, \
            "window pool accounting leak"
        want: dict[int, int] = {}
        for t in self._tables.values():
            for b in t:
                if b != SCRATCH_BLOCK:
                    want[b] = want.get(b, 0) + 1
        assert want == self._mapped, "window mappings drifted"
        for b in self._pinned:
            want[b] = want.get(b, 0) + 1
        assert want == self._refcount, "window references drifted"
        for slot, t in self._tables.items():
            held = sum(b != SCRATCH_BLOCK for b in t)
            assert held <= self.slot_blocks, \
                f"slot {slot} holds {held} window blocks"


class BlockManager:
    """Refcounted block pool + per-slot page tables + radix prefix cache.

    `refcount(blk)` reports LIVE holders (slots mapping the block); the
    cache pin is internal bookkeeping and excluded. `blocks_in_use`
    likewise counts live blocks only — a drained pool reads 0 even while
    the cache retains (evictable) blocks.
    """

    def __init__(self, num_blocks: int, block_size: int, table_width: int,
                 sharing: bool = True, cross_time: bool = False,
                 window_blocks: int = 0, window: int = 0,
                 window_span: int = 1, window_aligned: bool = False):
        """`window_blocks` > 0: the graph has layers that attend a window
        of `window` keys; their rows live in a window group of that many
        blocks (module docstring), a step writing at most `window_span`
        rows of a slot. `window_aligned`: the window begins at a multiple
        of `window` and does not slide (WindowGroup.aligned)."""
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (scratch + 1 allocatable), got "
                f"{num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.table_width = int(table_width)
        self.sharing = bool(sharing)  # False = paged-without-reuse ablation
        self.cross_time = bool(cross_time)  # False = live sharing only
        # LIFO free list: hot blocks are reused while still cached
        self._free = list(range(num_blocks - 1, 0, -1))
        # RAW references: live slot mappings + (if cached) one cache pin
        self._refcount: dict[int, int] = {}
        self._live = 0  # blocks with >= 1 live (non-pin) reference
        # admission reservations (worst-case fresh blocks per resident),
        # keyed by request id until bind_reservation moves the key to the
        # slot index: Σ reservations <= free blocks at all times, so a
        # decode write can NEVER exhaust the pool mid-flight — admission
        # is the only place pool pressure is felt (FCFS head-blocking)
        self._reserved: dict = {}
        # cached blocks an admission counted on not having to draw
        # (reserve with a prompt), keyed like the reservation until the
        # slot maps them: eviction passes them over
        self._held: dict = {}
        # slot index -> logical->physical list (allocated prefix only)
        self._tables: dict[int, list[int]] = {}
        self.cache = RadixPrefixCache(block_size) if self.sharing else None
        self.stats = PagedStats()
        self.window = None
        # cached global block -> the window block pinned beside it, and
        # the cache's clock when it was pinned
        self._wpins: dict[int, int] = {}
        self._wpin_tick: dict[int, int] = {}
        if window_blocks:
            self.window = WindowGroup(window_blocks, block_size, window,
                                      window_span, self._drop_window_pin,
                                      window_aligned)

    # ------------------------------------------------------------ queries

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        """Blocks held by at least one live slot (cache-only excluded)."""
        return self._live

    @property
    def cached_blocks(self) -> int:
        """Blocks the radix cache holds a pin on (live-shared or not)."""
        return 0 if self.cache is None else len(self.cache.pinned)

    @property
    def cached_only_blocks(self) -> int:
        """Cached blocks whose pin is the sole reference — the evictable
        set the admission gate can reclaim."""
        if self.cache is None:
            return 0
        return sum(1 for b in self.cache.pinned
                   if self._refcount.get(b, 0) == 1)

    def table(self, slot: int) -> list[int]:
        """The slot's page table padded to table_width with SCRATCH (the
        row the engine feeds the device op)."""
        t = self._tables.get(slot, [])
        return t + [SCRATCH_BLOCK] * (self.table_width - len(t))

    def window_table(self, slot: int) -> list[int]:
        """The slot's page table in the window group, padded like
        `table`: the scratch block wherever the slot holds nothing (what
        fell behind its window, what it has not reached)."""
        return self.window.table(slot, self.table_width)

    def _pinned(self, block: int) -> bool:
        return self.cache is not None and block in self.cache.pinned

    def refcount(self, block: int) -> int:
        """LIVE holders of `block` (the cache pin is excluded)."""
        rc = self._refcount.get(block, 0)
        return rc - 1 if rc and self._pinned(block) else rc

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case fresh blocks a request can consume over its life:
        every block of [0, prompt + new), CAPPED at the logical capacity
        (table_width) — generation physically stops at max_seq rows (the
        scheduler's `length` completion rule), so a huge max_new_tokens
        must not inflate the reservation past what the slot can ever
        write. Prefix sharing only ever LOWERS the real draw (a slot's
        shared blocks cost nothing, and at most one COW replaces a shared
        block with a fresh one), so reserving this at admission makes
        mid-flight exhaustion impossible."""
        return min(-(-(prompt_len + max_new_tokens) // self.block_size),
                   self.table_width)

    @property
    def reserved_total(self) -> int:
        return sum(self._reserved.values())

    def reserve(self, request_id, prompt_len: int,
                max_new_tokens: int, prompt=None) -> bool:
        """Admission gate: reserve the request's worst case against the
        pool, evicting cold cache leaves first when the free list alone
        cannot cover it. False = not enough headroom even after eviction
        (the caller keeps the request queued — FCFS head-blocking, so
        admission order never depends on pool pressure in a way that
        could reorder token streams).

        With the `prompt` itself (and a cross-time cache) the worst case
        leaves out the cached blocks the request will never write: those
        wholly before its first write, which is at the end of the cached
        extent. What it matched is held against eviction until the slot
        maps it, so the draw cannot come out higher. A 30,000-token history in the
        cache then costs its follow-up the blocks of the new turn and the
        reply, not a second history's worth of free pool."""
        needed = self.blocks_needed(prompt_len, max_new_tokens)
        held = []
        if prompt is not None and self.cache is not None and self.cross_time:
            covered, blocks = self._usable(
                prompt, *self.cache.match(prompt, peek=True))
            needed -= min(covered, prompt_len - 1) // self.block_size
            held = blocks  # the tail it will copy on its first write too
        self._held[("req", request_id)] = held
        headroom = self.free_blocks - self.reserved_total
        if headroom < needed:
            self._evict_blocks(needed - headroom)
        if (self.free_blocks - self.reserved_total < needed
                or (self.window is not None
                    and not self.window.reserve(("req", request_id)))):
            del self._held[("req", request_id)]
            return False
        self._reserved[("req", request_id)] = needed
        return True

    def bind_reservation(self, request_id, slot: int):
        """Move an admission reservation onto the slot that won it (the
        scheduler assigns slots after the gate passes)."""
        n = self._reserved.pop(("req", request_id), None)
        if n is not None:
            self._reserved[slot] = n
        held = self._held.pop(("req", request_id), None)
        if held:
            self._held[slot] = held
        if self.window is not None:
            self.window.bind_slot(("req", request_id), slot)

    # --------------------------------------------------------- refcounts

    def _map(self, block: int):
        """One more live holder of `block`."""
        if self.refcount(block) == 0:
            self._live += 1
        self._refcount[block] = self._refcount.get(block, 0) + 1

    def _unmap(self, block: int):
        """One live holder of `block` gone; frees at zero references."""
        if self.refcount(block) == 1:
            self._live -= 1
        n = self._refcount[block] - 1
        if n == 0:
            del self._refcount[block]
            self._free.append(block)
        else:
            self._refcount[block] = n

    def _unpin_free(self, block: int):
        """Drop the cache pin's reference (the node is already out of the
        cache); frees at zero."""
        n = self._refcount[block] - 1
        if n == 0:
            del self._refcount[block]
            self._free.append(block)
        else:
            self._refcount[block] = n

    def _evict_blocks(self, need: int) -> int:
        """Evict LRU cache leaves until `need` blocks are freed (or the
        cache runs out of freeable nodes). A victim whose block a live
        slot still maps frees nothing — it only leaves the cache (and
        unblocks a freeable ancestor)."""
        if self.cache is None or need <= 0:
            return 0
        held = {b for blocks in self._held.values() for b in blocks}
        freed = 0
        while freed < need:
            before = len(self._free)
            blk = self.cache.evict_lru(
                lambda b: self._refcount.get(b, 0) == 1 and b not in held,
                keep=held)
            if blk is None:
                break
            self._unpin_window(blk)
            self._unpin_free(blk)
            self.stats.radix_evictions += 1
            if len(self._free) > before:
                freed += 1
                self.stats.radix_evicted_blocks += 1
        return freed

    # ------------------------------------------------------ window group

    def _unpin_window(self, block: int):
        """The cached node of global `block` leaves the cache: so does the
        window block pinned beside it."""
        wblk = self._wpins.pop(block, None)
        if wblk is not None:
            del self._wpin_tick[block]
            self.window.unpin(wblk)

    def _drop_window_pin(self):
        """The window group has no free block: a cached node whose window
        block nothing else holds gives it up, first those no prompt was
        matched through since they were pinned (a finished request's
        question, which nothing can match again), leaves before their
        parents, the least recently used first; then the others likewise.
        A leaf that nothing else holds leaves the cache with it (a prompt
        matched into it could not be continued from there, and a sibling
        that can be would lose the match to it); any other node stays (its
        global block still encodes its rows), and a prompt is matched
        through it only as far as `_usable` allows."""
        held = {b for blocks in self._held.values() for b in blocks}
        node = min(
            (self.cache.pinned[g] for g, w in self._wpins.items()
             if self.window._refcount.get(w, 0) == 1 and g not in held),
            key=lambda n: (n.last_used > self._wpin_tick[n.block],
                           bool(n.children), n.last_used), default=None)
        if node is None:
            return
        self._unpin_window(node.block)
        self.stats.window_pins_dropped += 1
        if not node.children and self._refcount.get(node.block, 0) == 1:
            self.cache.drop_block(node.block)
            self._unpin_free(node.block)
            self.stats.radix_evictions += 1
            self.stats.radix_evicted_blocks += 1

    def _usable(self, prompt, covered: int, blocks: list):
        """(covered, blocks) of a match cut back to the longest extent at
        which every group holds what a continuation needs: the global
        group every block (what `blocks` is), the window group the blocks
        of the `window - 1` rows before that length. Tried: the whole
        extent (less the prompt's last token, which is always computed),
        then every block boundary under it."""
        w = self.window
        if w is None or not covered:
            return covered, blocks
        bs = self.block_size

        def held(upto):
            return upto > 0 and all(
                blocks[lb] in self._wpins
                for lb in range(w.first_block(upto), (upto - 1) // bs + 1))

        at = min(covered, len(prompt) - 1)
        if w.aligned and not held(at) and blocks[-1] not in self._wpins:
            # the extent ends inside a node that gave its window block up
            # (a finished request's tail that begins as this prompt's
            # does): a sibling that holds one and begins so too, the
            # history's own end, is the end to continue from (an aligned
            # window's histories pin their last blocks alone, so a block
            # boundary further back is no end; a sliding window's match
            # falls back to one, as it did)
            covered, blocks = self._pinned_end(prompt, covered, blocks)
            at = min(covered, len(prompt) - 1)
        for upto in (at, *range((at - 1) // bs * bs, 0, -bs)):
            if held(upto):
                if upto == at:
                    return covered, blocks
                return upto, blocks[:upto // bs]  # a block boundary
        return 0, []

    def _pinned_end(self, prompt, covered: int, blocks: list):
        """(covered, blocks) with the match's last node exchanged for the
        sibling that holds a window block and shares the longest run with
        the prompt there; as they were where there is none."""
        lo = (len(blocks) - 1) * self.block_size
        shared, block = self.cache.sibling(
            blocks[-1], prompt[lo:lo + self.block_size],
            self._wpins.__contains__)
        if not shared:
            return covered, blocks
        return lo + shared, blocks[:-1] + [block]

    # ------------------------------------------------------------ intake

    def match_prefix(self, prompt) -> int:
        """Covered token count of the longest cached extent of `prompt`
        that every group can continue from (a pure peek: no stats, no LRU
        touch)."""
        if self.cache is None:
            return 0
        return self._usable(prompt, *self.cache.match(prompt, peek=True))[0]

    def admit(self, slot: int, prompt: list[int]) -> int:
        """Build `slot`'s page table: map every block of the longest
        cached extent (refcount++), leave the rest for prefill writes to
        allocate. Called LAZILY — at the slot's first prefill chunk, not
        at admission — so a burst of same-prefix requests still shares:
        by the time the second request prefills, the first has computed
        and registered its blocks. Returns the prefill cursor: prompt
        tokens whose K/V need no recomputation, capped at len(prompt) - 1
        because the final token's logits row samples the first generated
        token (its re-write into a fully-shared block is the first
        COW)."""
        if slot in self._tables:
            raise ValueError(f"slot {slot} already holds a table")
        L = len(prompt)
        self.stats.prefix_queries += 1
        if self.cache is not None:
            covered, blocks = self._usable(prompt, *self.cache.match(prompt))
        else:
            covered, blocks = 0, []
        # a matched block with no live holder was served across time —
        # its residents exited and only the cache pin kept it
        cross = any(self._refcount.get(b, 0) == 1 for b in blocks)
        table: list[int] = []
        for blk in blocks:
            # full blocks, plus a partially-matched tail (mapped
            # read-only; the first write into it COWs under the pin)
            self._map(blk)
            table.append(blk)
        self._tables[slot] = table
        self._held.pop(slot, None)  # mapped now: a live reference holds them
        skip = min(covered, L - 1)
        if self.window is not None:
            self.window.admit(slot, {
                lb: self._wpins[blocks[lb]]
                for lb in range(self.window.first_block(skip),
                                (skip - 1) // self.block_size + 1)
                if skip > 0})
        self.stats.prompt_tokens += L
        self.stats.shared_tokens += skip
        if skip:
            self.stats.prefix_hits += 1
            if cross:
                self.stats.cross_time_hits += 1
        self._note_peak()
        return skip

    # ------------------------------------------------------------ writes

    def _note_peak(self):
        if self._live > self.stats.blocks_in_use_peak:
            self.stats.blocks_in_use_peak = self._live

    def _alloc(self, slot: int) -> int:
        if not self._free:
            # the admission reservations make this unreachable; evict
            # rather than die if an embedder drives the manager directly
            self._evict_blocks(1)
        if not self._free:
            raise RuntimeError(
                "paged KV pool exhausted — the admission reservations "
                "(reserve/blocks_needed) must prevent this")
        blk = self._free.pop()
        self._refcount[blk] = 1
        self._live += 1
        if slot in self._reserved:
            self._reserved[slot] = max(0, self._reserved[slot] - 1)
        self._note_peak()
        return blk

    def ensure_writable(self, slot: int, positions) -> list[CopyPlan]:
        """Guarantee every logical block covering `positions` is owned
        solely (one live reference, no pin) by `slot`, allocating fresh
        blocks past the table end and COW-copying referenced ones.
        Returns the copies the engine must apply to the device pool
        BEFORE the step that writes. A CACHED block always COWs (the pin
        keeps its raw count above one), so published prompt content is
        immutable — decode extension can never poison the cache."""
        table = self._tables.get(slot)
        if table is None:
            raise ValueError(f"slot {slot} has no table")
        bs = self.block_size
        copies: list[CopyPlan] = []
        for lb in sorted({int(p) // bs for p in positions}):
            if lb >= self.table_width:
                raise ValueError(
                    f"position past the logical capacity "
                    f"({self.table_width * bs} rows)")
            while len(table) <= lb:
                table.append(self._alloc(slot))
            blk = table[lb]
            if self._refcount.get(blk, 0) > 1:
                fresh = self._alloc(slot)
                self._unmap(blk)
                table[lb] = fresh
                copies.append(CopyPlan(src=blk, dst=fresh))
                self.stats.cow_copies += 1
                self._maybe_drop_cached(blk)
        if self.window is not None:
            copies += self.window.ensure_writable(slot, positions,
                                                  self.stats)
        return copies

    def register_prompt(self, slot: int, prompt: list[int]):
        """Publish `slot`'s prompt blocks into the radix cache (called
        once when its prefill completes), keyed on the PROMPT extent only
        — decode-written rows are never published (any later write into a
        published block COWs away from it). Exact-run incumbents keep
        their entry; newly inserted nodes pin their blocks."""
        if self.cache is None:
            return
        table = self._tables.get(slot, [])
        for blk in self.cache.insert(prompt, table):
            self._refcount[blk] = self._refcount.get(blk, 0) + 1
        if self.window is not None:
            # the window blocks the slot holds at its prompt's end go
            # beside the nodes of the same rows (new ones and incumbents
            # that had none): the extent is matchable where they are
            # (under an aligned window only those a continuation of the
            # whole prompt reads: a prompt that ends where a window does
            # pins none)
            wtable = self.window.table(slot, self.table_width)
            keep = (self.window.first_block(len(prompt))
                    if self.window.aligned else 0)
            for lb, node in enumerate(self.cache.path(prompt)):
                wblk = wtable[lb]
                if (lb >= keep and wblk != SCRATCH_BLOCK
                        and node.block not in self._wpins
                        and wblk not in self.window._pinned):
                    self._wpins[node.block] = wblk
                    self._wpin_tick[node.block] = node.last_used
                    self.window.pin(wblk)

    # ------------------------------------------------------------ release

    def release(self, slot: int):
        """Drop the slot's table; refcounts decrement and blocks reaching
        zero references return to the free list. With `cross_time` the
        cache keeps its pinned blocks (that is the point — the prefix
        outlives the resident); without it, a block left holding only its
        pin is dropped from the cache and freed immediately (the old
        live-residents-only semantics)."""
        self._reserved.pop(slot, None)
        self._held.pop(slot, None)
        if self.window is not None:
            self.window.release(slot)
        table = self._tables.pop(slot, None)
        if table is None:
            return
        for blk in table:
            self._unmap(blk)
            self._maybe_drop_cached(blk)

    def _maybe_drop_cached(self, block: int):
        """Without `cross_time`, a block left holding only its cache pin
        is dropped and freed on the spot — the old live-residents-only
        sharing semantics (a prefix dies with its last holder)."""
        if (not self.cross_time and self.cache is not None
                and self._refcount.get(block, 0) == 1
                and block in self.cache.pinned):
            self.cache.drop_block(block)
            self.stats.radix_evictions += 1
            self.stats.radix_evicted_blocks += 1
            self._unpin_window(block)
            self._unpin_free(block)

    def check_invariants(self):
        """Debug/test hook: every block is free xor referenced, the
        scratch block is neither, table entries have a live reference,
        the live-block counter reproduces from the raw counts, and the
        radix tree agrees with the pin accounting."""
        free = set(self._free)
        assert SCRATCH_BLOCK not in free
        assert SCRATCH_BLOCK not in self._refcount
        assert not (free & set(self._refcount)), "block both free and live"
        for slot, table in self._tables.items():
            for blk in table:
                assert self.refcount(blk) >= 1, \
                    f"slot {slot} maps block {blk} with no live reference"
        counted = sum(1 for _ in self._refcount)
        assert counted + len(free) == self.num_blocks - 1, \
            "pool accounting leak"
        live = sum(1 for b in self._refcount if self.refcount(b) > 0)
        assert live == self._live, \
            f"live counter drifted: cached {self._live}, actual {live}"
        assert self.reserved_total <= self.free_blocks, \
            "reservations exceed the free pool"
        if self.cache is not None:
            self.cache.check_invariants()
            for blk in self.cache.pinned:
                assert self._refcount.get(blk, 0) >= 1, \
                    f"cache pins unreferenced block {blk}"
            if not self.cross_time:
                assert self.cached_only_blocks == 0, \
                    "cross_time off but cache retains resident-free blocks"
        if self.window is not None:
            self.window.check_invariants()
            assert set(self._wpins.values()) == self.window._pinned
            assert all(g in self.cache.pinned for g in self._wpins), \
                "window block pinned beside a node that left the cache"
