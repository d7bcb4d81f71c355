"""Fixed-size KV block pool — the paged-cache allocator.

HBM holds ONE preallocated pool of ``num_blocks`` KV blocks per engine
(``(layers, num_blocks, block_size, kv_heads, head_dim)`` for K and V);
sequences own ``ceil(len / block_size)`` block ids each, recorded in a
per-sequence block table, so resident cache memory is ``Σ ceil(len/block)``
blocks instead of ``batch × T_max`` dense caches.

Block 0 is the reserved TRASH block: padding rows of a bucketed batch and
padded tail entries of short rows point their table slots at it, so the
compiled programs can scatter unconditionally — trash is written freely and
never read (the live mask excludes every position it could back).

The allocator is free-list + owned-set bookkeeping with hard invariants:
allocating more than is free returns ``None`` (the scheduler turns that into
queue backpressure or preemption, never a crash), freeing an unowned id
raises (double-free), and ``check()`` asserts conservation. Engine-thread
only — the scheduler is the single owner, so no lock is needed here.

Under HBM pressure (fault/memory.py recovery ladder) the scheduler PARKS
blocks: :meth:`park` moves free blocks to a reserved set that ``alloc``
cannot see, shrinking admission headroom so continuous batching backs off
to a smaller resident working set — backpressure, never a crash. ``check``
counts parked blocks in the conservation invariant; :meth:`unpark` gives
them back once pressure clears.

Blocks are REFCOUNTED (prefix-cache KV sharing): ``alloc`` hands out blocks
at refcount 1, :meth:`share` bumps an owned block so several sequences (or
the engine's prefix index) can map the same physical block, and ``free``
decrements — the block returns to the free list only when the last
reference drops. Freeing an unowned id still raises (double-free), and
``park`` only ever draws from the free list, so a block with live
references can structurally never be parked — PR 14's OOM pool-shrink is
safe under sharing by construction.

The bookkeeping is SNAPSHOTTABLE (serving state durability): ``snapshot``
captures free list, ownership, refcounts, and parked set in O(blocks) plus
a CRC over the canonical encoding, and ``restore`` rebuilds a pool from a
capture — re-running ``check()`` plus structural validation so a torn or
tampered snapshot surfaces as a structured :class:`SnapshotError`, never a
silently-wrong allocator.

Tensor-parallel serving does not change ANY of this: the device-side KV
arrays are sharded over the mesh on the kv_heads axis (each chip owns
``kv_heads/tp`` of every block), but a block id names the same slot on
every shard, so this host-side allocator — free list, refcounts, parked
set, snapshots, conservation — stays REPLICATED and tp-oblivious. One
bookkeeping truth drives ``tp`` physical shards.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Optional

from ..profiler import counter_inc

__all__ = ["PagePool", "SnapshotError", "TRASH_BLOCK"]

TRASH_BLOCK = 0

POOL_SNAPSHOT_VERSION = 1


class SnapshotError(RuntimeError):
    """A serving-state snapshot failed validation (torn capture, tampering,
    or an incompatible target) — callers fall back to re-prefill recovery
    rather than serving from suspect KV state."""


def _pool_crc(num_blocks: int, free, ref, parked) -> int:
    payload = (num_blocks, tuple(free), tuple(sorted(ref.items())),
               tuple(parked))
    return zlib.crc32(repr(payload).encode())


class PagePool:
    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("PagePool needs >= 2 blocks (block 0 is trash)")
        self.num_blocks = int(num_blocks)
        # LIFO free list: recently-freed blocks are re-used first (warm)
        self._free: List[int] = list(range(self.num_blocks - 1, TRASH_BLOCK, -1))
        self._owned = set()
        self._ref: Dict[int, int] = {}  # owned block id -> reference count
        # blocks withdrawn from circulation under memory pressure (park()):
        # invisible to alloc, still conserved by check()
        self._parked: List[int] = []

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._owned)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` block ids, or None when the pool can't cover them (the
        caller's backpressure signal — nothing is partially allocated)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self._owned.update(ids)
        for b in ids:
            self._ref[b] = 1
        counter_inc("serve_pages_allocated", n)
        return ids

    def share(self, ids) -> None:
        """Bump the refcount of already-owned blocks (prefix-cache sharing):
        each sharer later calls ``free`` once, and the block only returns to
        circulation when the last reference drops. Sharing an unowned id
        raises — a sharer can only piggyback on a live block."""
        for b in ids:
            if b not in self._owned:
                raise RuntimeError(f"PagePool: share of unowned block id {b}")
        for b in ids:
            self._ref[b] += 1

    def refcount(self, bid: int) -> int:
        """Current reference count of a block (0 = not owned)."""
        return self._ref.get(bid, 0)

    def free(self, ids) -> None:
        """Drop one reference per id; a block returns to the free list when
        its count hits zero. Freeing an unowned id raises (double-free)."""
        released = 0
        for b in ids:
            if b not in self._owned:
                raise RuntimeError(
                    f"PagePool: double-free or foreign block id {b}"
                )
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._owned.remove(b)
                self._free.append(b)
                released += 1
        counter_inc("serve_pages_freed", released)

    @property
    def parked_blocks(self) -> int:
        return len(self._parked)

    def park(self, n: int) -> int:
        """Withdraw up to ``n`` FREE blocks from circulation (HBM-pressure
        admission-headroom shrink): parked blocks are invisible to ``alloc``
        so the scheduler's backpressure engages at a smaller resident
        working set. Running sequences keep what they own — only future
        growth is throttled. Returns how many were actually parked (never
        drains the free list completely: one grow-block of headroom stays,
        so a lone running sequence can still finish)."""
        if n < 0:
            raise ValueError(f"park({n})")
        take = max(min(int(n), len(self._free) - 1), 0)
        for _ in range(take):
            self._parked.append(self._free.pop())
        if take:
            counter_inc("serve_pages_parked", take)
        return take

    def unpark(self, n: Optional[int] = None) -> int:
        """Return parked blocks to the free list (pressure cleared)."""
        take = len(self._parked) if n is None else min(int(n), len(self._parked))
        for _ in range(take):
            self._free.append(self._parked.pop())
        if take:
            counter_inc("serve_pages_unparked", take)
        return take

    def damage(self) -> None:
        """Chaos-only (``serve.pool_corrupt`` injection point): deliberately
        break conservation so the next ``free()`` of the damaged block (or
        ``check()``) raises — the engine's crash-containment path must turn
        a corrupt pool into failed-or-requeued handles, never a hang."""
        if self._owned:
            lost = next(iter(self._owned))
            self._owned.discard(lost)
            self._ref.pop(lost, None)
        elif self._free:
            self._free.append(self._free[-1])
        counter_inc("serve_pool_damaged")

    def check(self) -> None:
        """Conservation invariant: every non-trash block is exactly one of
        free, owned, or parked; every owned block carries a refcount >= 1
        and nothing else does (refcounts never leak past ownership)."""
        if len(self._free) + len(self._owned) + len(self._parked) \
                != self.num_blocks - 1:
            raise RuntimeError(
                f"PagePool leak: {len(self._free)} free + "
                f"{len(self._owned)} owned + {len(self._parked)} parked "
                f"!= {self.num_blocks - 1}"
            )
        circulating = set(self._free) | set(self._parked)
        if self._owned & circulating or len(circulating) != (
                len(self._free) + len(self._parked)):
            raise RuntimeError("PagePool: block in two states at once")
        if TRASH_BLOCK in self._owned or TRASH_BLOCK in circulating:
            raise RuntimeError("PagePool: trash block entered circulation")
        if set(self._ref) != self._owned:
            raise RuntimeError(
                "PagePool: refcount bookkeeping diverged from ownership"
            )
        if any(c < 1 for c in self._ref.values()):
            raise RuntimeError("PagePool: owned block with refcount < 1")

    # -- snapshot / restore (serving state durability) ----------------------

    def snapshot(self) -> dict:
        """O(blocks) consistent capture of the allocator bookkeeping.

        Caller contract: taken at a scheduler step boundary (or from a dead
        scheduler's frozen state) — the pool is engine-thread-only, so a
        boundary capture is consistent by construction. The CRC covers the
        canonical encoding; ``restore`` rejects any capture whose fields no
        longer match it (torn or tampered snapshot)."""
        snap = {
            "version": POOL_SNAPSHOT_VERSION,
            "num_blocks": self.num_blocks,
            "free": list(self._free),
            "ref": dict(self._ref),
            "parked": list(self._parked),
        }
        snap["crc"] = _pool_crc(self.num_blocks, self._free, self._ref,
                                self._parked)
        return snap

    @classmethod
    def restore(cls, snap: dict) -> "PagePool":
        """Rebuild a pool from a :meth:`snapshot` capture, or raise
        :class:`SnapshotError`. Validation is the extended ``check()``:
        CRC integrity, id ranges, duplicate detection, conservation, and
        refcount↔ownership agreement all must hold — a capture that fails
        any of them is rejected whole (the restored pool never escapes)."""
        try:
            if snap.get("version") != POOL_SNAPSHOT_VERSION:
                raise SnapshotError(
                    f"pool snapshot version {snap.get('version')!r} "
                    f"!= {POOL_SNAPSHOT_VERSION}"
                )
            num_blocks = int(snap["num_blocks"])
            free = [int(b) for b in snap["free"]]
            ref = {int(b): int(c) for b, c in snap["ref"].items()}
            parked = [int(b) for b in snap["parked"]]
        except SnapshotError:
            raise
        except Exception as e:
            raise SnapshotError(f"malformed pool snapshot: {e!r}") from e
        if _pool_crc(num_blocks, free, ref, parked) != snap.get("crc"):
            raise SnapshotError("pool snapshot CRC mismatch (torn capture)")
        ids = free + list(ref) + parked
        if any(b <= TRASH_BLOCK or b >= num_blocks for b in ids):
            raise SnapshotError("pool snapshot: block id out of range")
        if len(set(ids)) != len(ids):
            raise SnapshotError("pool snapshot: block in two states at once")
        pool = cls(num_blocks)
        pool._free = free
        pool._owned = set(ref)
        pool._ref = ref
        pool._parked = parked
        try:
            pool.check()
        except RuntimeError as e:
            raise SnapshotError(f"pool snapshot failed check(): {e}") from e
        counter_inc("serve_pool_restores")
        return pool
