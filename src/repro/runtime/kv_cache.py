"""Row-addressable, block-granular (paged) KV-cache pool for serving.

The decode KV cache is the serving path's single largest memory object, yet
the seed treated it as a per-group throwaway blob: every group called
``model.init_cache`` itself, prefill state was discarded, and the planner
never saw the bytes. This module gives the cache a single owner:

- :class:`CacheArena` — one bucket-shaped cache pytree whose *batch rows*
  are individually leasable. Rows at different generation depths coexist in
  one arena because the decode step takes a per-row position vector.
- :class:`BlockAllocator` — free-list of fixed-size *pages* inside an
  arena's sequence dimension (vLLM-style paging). Rows lease pages on
  demand as their position advances; a row's page table maps logical slot
  ``i`` to physical slot ``table[i // page] * page + i % page``.
- :class:`KVCachePool` — owns every arena: leases them to request groups,
  recycles fully-freed arenas (no reallocation), scatters prefill-produced
  cache rows into leased arenas (the prefill→decode handoff write), and
  accounts live bytes for the planner. A leased arena's free rows are where
  the scheduler lands mid-decode joins.

With ``page_size > 0`` the attention K/V entries lose their per-row
sequence dimension: one flat ``(L, n_pages * page, Kv, Dh)`` slot stack is
shared by every row of the arena, and each row only *commits* the pages its
request span actually needs. A 70-token request inside a 512-slot bucket
therefore pins ~2 pages, not 512 slots — the pool's live bytes (what the
planner sees, what the byte budget charges) become page-exact. Recurrent
rows (SSD state, RG-LRU state, conv tails, enc-dec cross K/V) keep their
single-state per-row fast path: they are O(1) in the sequence dimension and
paging them would buy nothing.

The pool's live bytes feed :class:`~repro.core.strategies.RuntimeStats`
(``cache_pool_bytes``): when the pool outgrows the plan's compile-time
cache statistic, dynamic recompilation triggers exactly like an
activation-watermark breach (``core.plan_cache.recompile_reasons``).

Budgets (``max_arenas`` / ``max_bytes``) bound the pool the way an HBM
reservation would: ``acquire`` refuses new leases beyond the budget (the
scheduler then queues the group — or joins its requests into free rows and
free pages of in-flight arenas instead, which is the whole point).
"""

from __future__ import annotations

import heapq
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class PoolMetrics:
    """Pool-level accounting surfaced through ``scheduler_summary``."""

    arenas_created: int = 0
    arenas_reused: int = 0      # leases served from the free pool
    arenas_denied: int = 0      # acquire refused by budget
    arenas_evicted: int = 0     # free arenas dropped (LRU cap / budget)
    rows_leased: int = 0
    rows_reused: int = 0        # leased rows whose arena had a prior tenant
    handoff_writes: int = 0     # prefill→decode row scatters
    peak_bytes: float = 0.0
    pages_leased: int = 0       # page-grant churn (cumulative)
    pages_freed: int = 0
    pages_denied: int = 0       # joins/admissions refused for lack of pages
    pages_reclaimed: int = 0    # pages (leased + undrawn reservation) given
    #                             back by early exits: cancel / eos / stop
    peak_pages: int = 0         # max concurrently committed pages
    # device bytes held by free arenas kept for recycling (a paged arena's
    # whole slot stack stays allocated though ``total_bytes`` charges none)
    pooled_bytes: float = 0.0

    def as_dict(self) -> Dict[str, float]:
        return {
            "arenas_created": self.arenas_created,
            "arenas_reused": self.arenas_reused,
            "arenas_denied": self.arenas_denied,
            "arenas_evicted": self.arenas_evicted,
            "rows_leased": self.rows_leased,
            "rows_reused": self.rows_reused,
            "handoff_writes": self.handoff_writes,
            "peak_bytes": self.peak_bytes,
            "pages_leased": self.pages_leased,
            "pages_freed": self.pages_freed,
            "pages_denied": self.pages_denied,
            "pages_reclaimed": self.pages_reclaimed,
            "peak_pages": self.peak_pages,
            "pooled_bytes": self.pooled_bytes,
        }


class BlockAllocator:
    """Free-list allocator over an arena's physical pages.

    ``reserve``/``alloc(from_reserve=True)`` split admission-time capacity
    checks from on-demand page grants: a row reserves every page its span
    can ever need when it is admitted (so mid-decode growth can never
    starve), then draws pages from that reservation one at a time as its
    position crosses page boundaries.

    Free pages live in a min-heap (lowest-index-first grants) mirrored by a
    set, so the per-tick grant path is O(log n) and double-free detection
    O(1) — long-context arenas can hold thousands of pages.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._heap: List[int] = list(range(n_pages))  # already heap-ordered
        self._free_set = set(self._heap)
        self.reserved = 0

    @property
    def free_count(self) -> int:
        return len(self._free_set)

    @property
    def available(self) -> int:
        """Pages admittable to *new* tenants (free minus reservations)."""
        return len(self._free_set) - self.reserved

    def alloc(self, n: int, *, from_reserve: bool = False) -> Optional[List[int]]:
        if from_reserve:
            if n > self.reserved or n > len(self._free_set):
                return None
            self.reserved -= n
        elif n > self.available:
            return None
        pages = [heapq.heappop(self._heap) for _ in range(n)]
        self._free_set.difference_update(pages)
        return pages

    def reserve(self, n: int) -> bool:
        if n > self.available:
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int) -> None:
        self.reserved = max(0, self.reserved - n)

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p in self._free_set:
                raise ValueError(f"page {p} double-freed")
            heapq.heappush(self._heap, p)
            self._free_set.add(p)


class CacheArena:
    """One bucket-shaped cache whose batch rows are individually leasable.

    ``cache`` is the live pytree threaded through the jitted decode step;
    the pool replaces it wholesale on handoff writes. Row bookkeeping
    (which rows are leased, which pages each row holds) is host-side — the
    device arrays never need to know, because free rows are simply masked
    out by their position vector and their outputs ignored.

    In paged mode (``page > 0``) the arena additionally owns a
    :class:`BlockAllocator` over ``n_pages`` physical pages and a device
    page-table ``tables`` of shape ``(batch, max_pages)`` int32 (sentinel
    ``n_pages`` marks unallocated entries; gathers through it are masked,
    scatters through it are dropped).
    """

    def __init__(self, batch: int, seq: int, cache: Dict[str, Any],
                 nbytes: float, *, page: int = 0, sc: int = 0,
                 n_pages: int = 0, page_nbytes: float = 0.0,
                 row_nbytes: float = 0.0, rotating: bool = False,
                 paged_keys: Sequence[str] = ()):
        self.batch = batch
        self.seq = seq
        self.cache = cache
        self.nbytes = nbytes            # full-capacity bytes (dense charge)
        self.generation = 0             # completed leases of this arena
        self._free: List[int] = list(range(batch))
        # -- paging state ---------------------------------------------------
        self.page = page
        self.sc = sc                    # logical cache slots per row
        self.n_pages = n_pages
        self.page_nbytes = page_nbytes  # bytes of one page across the stack
        self.row_nbytes = row_nbytes    # per-row bytes of non-paged entries
        self.rotating = rotating        # rotating-window slot semantics
        self.paged_keys = tuple(paged_keys)
        self.allocator = BlockAllocator(n_pages) if page else None
        self.max_pages = max(1, -(-sc // page)) if page else 0
        self._row_pages: Dict[int, List[int]] = {}
        self._row_reserved: Dict[int, int] = {}
        self._row_slots: Dict[int, int] = {}   # valid slots (frag metric)
        if page and n_pages:
            self._tables_np = np.full((batch, self.max_pages), n_pages,
                                      np.int32)
            self._tables = jnp.asarray(self._tables_np)
            self._tables_dirty = False
        else:
            # no paged entries (pure-recurrent families): rows are the only
            # granularity, accounting stays row-exact, tables are unused
            self._tables_np = None
            self._tables = None
            self._tables_dirty = False

    # -- row bookkeeping ---------------------------------------------------
    @property
    def rows_free(self) -> int:
        return len(self._free)

    @property
    def rows_used(self) -> int:
        return self.batch - len(self._free)

    def alloc_rows(self, n: int) -> Optional[List[int]]:
        """Lease ``n`` rows (lowest-index first); None if not enough free."""
        if n > len(self._free):
            return None
        self._free.sort()
        rows, self._free = self._free[:n], self._free[n:]
        return rows

    def free_rows(self, rows: Sequence[int]) -> None:
        for r in rows:
            if r in self._free:
                raise ValueError(f"row {r} double-freed")
            self._free.append(r)

    # -- donation handoff --------------------------------------------------
    def relinquish(self) -> Dict[str, Any]:
        """Hand the cache pytree to a (possibly donating) decode step: the
        arena drops its reference so the step argument is the only live
        handle — a donating jit then consumes the buffers in place, and no
        stale reference can read them mid-step. The tick must
        :meth:`adopt` the step's cache output before anything else touches
        the arena."""
        if self.cache is None:
            raise RuntimeError(
                "arena cache already relinquished and not re-adopted")
        cache, self.cache = self.cache, None
        return cache

    def adopt(self, cache: Dict[str, Any]) -> None:
        """Re-adopt the decode step's cache output as the arena's live
        pytree (the other half of :meth:`relinquish`)."""
        if self.cache is not None:
            raise RuntimeError("arena already holds a live cache pytree")
        self.cache = cache

    # -- paging ------------------------------------------------------------
    @property
    def pages_leased(self) -> int:
        return sum(len(p) for p in self._row_pages.values())

    @property
    def pages_committed(self) -> int:
        """Leased plus reserved pages — the arena's committed capacity."""
        if self.allocator is None:
            return 0
        return self.pages_leased + self.allocator.reserved

    def span_pages(self, span: int) -> int:
        """Pages a row occupying ``span`` logical slots needs end-to-end."""
        if not self.page or not self.n_pages:
            return 0
        return -(-min(max(1, span), self.sc) // self.page)

    def live_nbytes(self) -> float:
        """Page-exact committed bytes: leased+reserved pages plus the
        per-row (recurrent / cross) state of leased rows."""
        if not self.page:
            return self.nbytes
        return (self.pages_committed * self.page_nbytes
                + self.rows_used * self.row_nbytes)

    def used_slots(self) -> int:
        return sum(self._row_slots.values())

    @property
    def tables(self):
        """Device page-table array, re-uploaded lazily: row admissions and
        page grants mutate the host table and only mark it dirty, so a
        batch of per-row updates costs one host->device transfer at the
        next decode step instead of one per row."""
        if self._tables_dirty:
            self._tables = jnp.asarray(self._tables_np)
            self._tables_dirty = False
        return self._tables

    def _sync_tables(self) -> None:
        self._tables_dirty = True

    def admit_row(self, row: int, prompt: int, span: int,
                  eager: bool = False) -> List[int]:
        """Commit a row's paging state: lease pages covering its initial
        valid slots (the prompt plus the first decode write — or the whole
        span with ``eager``) and reserve the rest of its span so on-demand
        growth can never starve mid-decode. Returns the leased pages."""
        if not self.page or not self.n_pages:
            return []
        total = self.span_pages(span)
        init_slots = min(span, self.sc) if eager else min(prompt + 1, self.sc)
        init_pages = min(total, -(-init_slots // self.page))
        if self.allocator.available < total:
            raise RuntimeError(
                f"KV page invariant violated: row {row} needs {total} pages "
                f"but arena {self.batch}x{self.seq} has only "
                f"{self.allocator.available} available "
                f"({self.allocator.free_count} free, "
                f"{self.allocator.reserved} reserved)")
        pages = self.allocator.alloc(init_pages)
        self.allocator.reserve(total - init_pages)
        self._row_pages[row] = list(pages)
        self._row_reserved[row] = total - init_pages
        self._row_slots[row] = init_slots
        self._tables_np[row, :len(pages)] = pages
        self._sync_tables()
        return pages

    def ensure_slot(self, row: int, lslot: int) -> Optional[int]:
        """Grant the page covering logical slot ``lslot`` to ``row`` from
        its admission-time reservation (no-op when already granted).
        Returns the newly granted physical page, if any."""
        if not self.page or not self.n_pages:
            return None
        lp = lslot // self.page
        pages = self._row_pages.get(row)
        if pages is None:
            raise RuntimeError(f"row {row} decodes without page admission")
        self._row_slots[row] = min(self.sc, max(self._row_slots[row],
                                                lslot + 1))
        if lp < len(pages):
            return None
        if lp != len(pages):
            raise RuntimeError(
                f"row {row} skipped a page boundary: wants logical page "
                f"{lp}, holds {len(pages)}")
        got = self.allocator.alloc(1, from_reserve=True)
        if got is None:
            raise RuntimeError(
                f"KV page reservation invariant violated: row {row} has no "
                f"reserved page left for logical page {lp}")
        pages.append(got[0])
        self._row_reserved[row] -= 1
        self._tables_np[row, lp] = got[0]
        self._sync_tables()
        return got[0]

    def reserved_for(self, rows: Sequence[int]) -> int:
        """Undrawn span-reservation pages still held for ``rows`` — the
        capacity an early exit hands back without it ever being leased."""
        return sum(self._row_reserved.get(r, 0) for r in rows)

    def release_row_pages(self, rows: Sequence[int]) -> int:
        """Return rows' pages (and outstanding reservations) to the
        allocator; returns how many leased pages were freed."""
        if not self.page or not self.n_pages:
            return 0
        freed = 0
        for r in rows:
            pages = self._row_pages.pop(r, None)
            if pages is None:
                continue
            self.allocator.free(pages)
            self.allocator.unreserve(self._row_reserved.pop(r, 0))
            self._row_slots.pop(r, None)
            self._tables_np[r, :] = self.n_pages
            freed += len(pages)
        if freed:
            self._sync_tables()
        return freed

    def phys_slots(self, rows: Sequence[int], sc: Optional[int] = None
                   ) -> np.ndarray:
        """(len(rows), sc) physical slot index per logical slot, with the
        out-of-range sentinel for slots on unallocated pages (host-side;
        used by the handoff scatter and row zeroing)."""
        sc = self.sc if sc is None else sc
        tab = self._tables_np[np.asarray(list(rows), np.int32)]
        i = np.arange(sc)
        phys = tab[:, np.minimum(i // self.page, self.max_pages - 1)]
        return phys * self.page + (i % self.page)[None, :]


class KVCachePool:
    """Single owner of decode-cache construction for a serving session.

    ``max_arenas`` / ``max_bytes`` (0 = unbounded) cap the pool;
    ``acquire(..., force=True)`` overrides the cap so a scheduler with no
    in-flight work can always make progress. Fully-freed arenas are kept
    for recycling up to ``max_free`` buckets (LRU-evicted beyond that, and
    evicted early whenever their bytes stand between a new lease and the
    budget) — retired shape buckets cannot pin HBM forever.

    ``page_size > 0`` turns on block-granular paging: attention K/V becomes
    a flat per-arena slot stack, rows commit only the pages their span
    needs, and ``live_bytes`` (what the byte budget charges and the planner
    observes) is page-exact instead of bucket-shaped.
    """

    def __init__(self, model, *, max_arenas: int = 0, max_bytes: float = 0.0,
                 max_free: int = 4, page_size: int = 0):
        self.model = model
        self.max_arenas = max_arenas
        self.max_bytes = max_bytes
        self.max_free = max(1, max_free)
        self.page_size = max(0, int(page_size))
        self.metrics = PoolMetrics()
        self._leased: List[CacheArena] = []
        # LRU order: least-recently released first (eviction order)
        self._pooled: List[CacheArena] = []
        self._params: Dict[tuple, tuple] = {}   # (b, s) -> paging params

    @property
    def paged(self) -> bool:
        return self.page_size > 0

    # -- sizing ------------------------------------------------------------
    def arena_bytes(self, batch: int, seq: int) -> float:
        """Exact bytes of one dense (batch, seq) arena, from the model's
        cache entry specs (no array materialization)."""
        total = 0.0
        for shape, _axes, dt in self.model.cache_entries(batch, seq).values():
            total += math.prod(shape) * np.dtype(dt).itemsize
        return total

    def _arena_params(self, batch: int, seq: int):
        """(entries, sc, n_pages, page_nbytes, row_nbytes, nbytes) for a
        paged (batch, seq) arena — one cached spec walk, no array
        materialization."""
        key = (batch, seq)
        if key not in self._params:
            ent, n_pages, sc = self.model.paged_cache_entries(
                batch, seq, self.page_size)
            page_nbytes = 0.0
            row_nbytes = 0.0
            total = 0.0
            for k, (shape, _axes, dt) in ent.items():
                nb = math.prod(shape) * np.dtype(dt).itemsize
                total += nb
                if self.model.is_paged_cache_key(k):
                    page_nbytes += nb / max(1, n_pages)
                else:
                    row_nbytes += nb / batch
            self._params[key] = (ent, sc, n_pages, page_nbytes, row_nbytes,
                                 total)
        return self._params[key]

    def span_pages(self, seq: int, span: int) -> int:
        """Pages one row of a ``seq``-bucket arena needs for ``span``."""
        if not self.paged:
            return 0
        _ent, sc, n_pages, _pb, _rb, _total = self._arena_params(1, seq)
        if not n_pages:
            return 0
        return -(-min(max(1, span), sc) // self.page_size)

    def member_bytes(self, seq: int, batch_rows: int, span: int) -> float:
        """Page-exact bytes one member commits: its rows' recurrent state
        plus its span's pages per row (the admission/join budget unit)."""
        if not self.paged:
            return 0.0
        _ent, sc, _n, page_nbytes, row_nbytes = self._arena_params(1, seq)[:5]
        pages = self.span_pages(seq, span)
        return batch_rows * (row_nbytes + pages * page_nbytes)

    def live_bytes(self) -> float:
        """Bytes currently committed to request groups (page-exact when
        paged: leased+reserved pages plus leased rows' recurrent state)."""
        return sum(a.live_nbytes() for a in self._leased)

    def bytes_room(self) -> float:
        """Byte budget headroom for further commitments (inf: unbounded)."""
        if not self.max_bytes:
            return math.inf
        return max(0.0, self.max_bytes - self.live_bytes())

    def total_bytes(self) -> float:
        """Leased plus pooled-free bytes (what the pool actually charges:
        page-exact for paged arenas — a fully-freed paged arena holds no
        committed pages, so recycling it is free)."""
        return self.live_bytes() + sum(a.live_nbytes() for a in self._pooled
                                       if not a.page)

    @property
    def arena_count(self) -> int:
        return len(self._leased) + len(self._pooled)

    def occupancy(self) -> float:
        """Fraction of leased-arena rows holding live requests."""
        total = sum(a.batch for a in self._leased)
        used = sum(a.rows_used for a in self._leased)
        return used / total if total else 0.0

    def slot_utilization(self) -> float:
        """Fraction of leased page slots holding valid cache entries (the
        internal-fragmentation complement, at page-grant granularity)."""
        leased = sum(a.pages_leased for a in self._leased) * self.page_size
        used = sum(a.used_slots() for a in self._leased)
        return used / leased if leased else 1.0

    def pages_live(self) -> int:
        return sum(a.pages_committed for a in self._leased)

    # -- lease lifecycle ---------------------------------------------------
    def _evict_free(self, count: int = 1) -> int:
        """Drop up to ``count`` least-recently-released free arenas (their
        device buffers go with them). Returns how many were evicted."""
        n = min(count, len(self._pooled))
        if n:
            self.metrics.pooled_bytes -= sum(a.nbytes for a in self._pooled[:n])
            del self._pooled[:n]
            self.metrics.arenas_evicted += n
        return n

    def _budget_blocks(self, nbytes: float) -> bool:
        if self.max_arenas and self.arena_count >= self.max_arenas:
            return True
        if self.max_bytes and self.total_bytes() + nbytes > self.max_bytes:
            return True
        return False

    def can_acquire(self, batch: int, seq: int,
                    demand_bytes: Optional[float] = None) -> bool:
        pooled = any((a.batch, a.seq) == (batch, seq) for a in self._pooled)
        if self.paged:
            need = (demand_bytes if demand_bytes is not None
                    else self.member_bytes(seq, batch, seq))
            if self.max_bytes and self.live_bytes() + need > self.max_bytes:
                return False
            if pooled:
                return True
            if self.max_arenas and len(self._leased) >= self.max_arenas:
                return False
            return True
        if pooled:
            return True
        nbytes = self.arena_bytes(batch, seq)
        if not self._budget_blocks(nbytes):
            return True
        # free arenas of other buckets are evictable — only *leased* memory
        # can genuinely refuse a lease
        if self.max_arenas and len(self._leased) >= self.max_arenas:
            return False
        if self.max_bytes and self.live_bytes() + nbytes > self.max_bytes:
            return False
        return True

    def _build_arena(self, batch: int, seq: int) -> CacheArena:
        if not self.paged:
            return CacheArena(batch, seq,
                              self.model.init_cache(batch, seq),
                              self.arena_bytes(batch, seq))
        ent, sc, n_pages, page_nbytes, row_nbytes, nbytes = \
            self._arena_params(batch, seq)
        cache = {k: jnp.zeros(s, d) for k, (s, _a, d) in ent.items()}
        paged_keys = tuple(k for k in ent
                           if self.model.is_paged_cache_key(k))
        rotating = self.model.decode_window(seq) > 0
        return CacheArena(batch, seq, cache, nbytes, page=self.page_size,
                          sc=sc, n_pages=n_pages, page_nbytes=page_nbytes,
                          row_nbytes=row_nbytes, rotating=rotating,
                          paged_keys=paged_keys)

    def acquire(self, batch: int, seq: int, *, zero: bool = False,
                force: bool = False,
                demand_bytes: Optional[float] = None) -> Optional[CacheArena]:
        """Lease a (batch, seq) arena. A fully-freed arena of the same
        bucket is recycled without reallocation; otherwise a fresh one is
        built — evicting idle free arenas first if they stand between the
        lease and the budget (None when still refused and not ``force``).

        ``zero``: clear recycled state, for tenants that decode from a zero
        cache instead of overwriting their rows via a handoff write.
        ``demand_bytes``: the page-exact bytes the lease will immediately
        commit (paged pools charge admissions, not arena capacity)."""
        arena = next((a for a in self._pooled
                      if (a.batch, a.seq) == (batch, seq)), None)
        if self.paged and not force:
            # paged budget: charge the admission's committed bytes (rows +
            # span pages), never the arena's worst-case capacity
            need = demand_bytes if demand_bytes is not None else 0.0
            blocked = bool(self.max_bytes
                           and self.live_bytes() + need > self.max_bytes)
            if arena is None and self.max_arenas:
                while (self.arena_count >= self.max_arenas
                       and self._evict_free()):
                    pass
                blocked = blocked or self.arena_count >= self.max_arenas
            if blocked:
                self.metrics.arenas_denied += 1
                return None
        if arena is not None:
            self._pooled.remove(arena)
            self.metrics.pooled_bytes -= arena.nbytes
            if zero:
                arena.cache = jax.tree.map(jnp.zeros_like, arena.cache)
            self.metrics.arenas_reused += 1
        else:
            if not self.paged:
                nbytes = self.arena_bytes(batch, seq)
                while self._budget_blocks(nbytes) and self._evict_free():
                    pass
                if not force and self._budget_blocks(nbytes):
                    self.metrics.arenas_denied += 1
                    return None
            arena = self._build_arena(batch, seq)
            self.metrics.arenas_created += 1
        self._leased.append(arena)
        self.metrics.peak_bytes = max(self.metrics.peak_bytes,
                                      self.total_bytes())
        return arena

    def alloc_rows(self, arena: CacheArena, n: int) -> Optional[List[int]]:
        rows = arena.alloc_rows(n)
        if rows is not None:
            self.metrics.rows_leased += n
            if arena.generation:
                self.metrics.rows_reused += n
        return rows

    def admit_request_rows(self, arena: CacheArena, n_rows: int, *,
                           prompt: int, span: int, eager: bool = False,
                           where: str = "admit_request_rows") -> List[int]:
        """The one paged-row admission sequence: lease ``n_rows`` rows and
        commit each one's paging state (prompt-covering pages now, span
        reservation for the rest — everything with ``eager``). Every
        admission path goes through here; the PR-4 recycled-arena ``zero=``
        leak was exactly this sequence drifting between ``PlanServer.handle``
        and the scheduler. A ``None`` row lease means admission accounting
        upstream (free-row check, join predicate) is out of sync with the
        arena — fail loudly with context instead of letting a ``TypeError``
        surface deep inside the caller."""
        rows = self.alloc_rows(arena, n_rows)
        if rows is None:
            raise RuntimeError(
                f"KV pool row invariant violated in {where}: request needs "
                f"{n_rows} rows but arena {arena.batch}x{arena.seq} has only "
                f"{arena.rows_free} free ({arena.rows_used} leased)")
        for r in rows:
            self.admit_row(arena, r, prompt=prompt, span=span, eager=eager)
        return rows

    def admit_row(self, arena: CacheArena, row: int, *, prompt: int,
                  span: int, eager: bool = False) -> None:
        """Commit a leased row's pages: lease the prompt-covering pages now
        (everything with ``eager``) and reserve the rest of its span."""
        if not arena.page:
            return
        pages = arena.admit_row(row, prompt, span, eager=eager)
        self.metrics.pages_leased += len(pages)
        self.metrics.peak_pages = max(self.metrics.peak_pages,
                                      self.pages_live())
        self.metrics.peak_bytes = max(self.metrics.peak_bytes,
                                      self.total_bytes())

    def ensure_decode_slots(self, arena: CacheArena, rows: Sequence[int],
                            pos: int) -> None:
        """Grant the page covering the next write position to ``rows``
        (no-op off-page-boundary; draws from admission reservations)."""
        if not arena.page or not arena.n_pages:
            return
        if not arena.rotating and pos >= arena.sc:
            return  # out-of-capacity writes drop; nothing to grant
        lslot = pos % arena.sc if arena.rotating else pos
        granted = 0
        for r in rows:
            if arena.ensure_slot(r, lslot) is not None:
                granted += 1
        if granted:
            self.metrics.pages_leased += granted
            self.metrics.peak_pages = max(self.metrics.peak_pages,
                                          self.pages_live())

    def free_rows(self, arena: CacheArena, rows: Sequence[int], *,
                  early: bool = False) -> None:
        """Return rows (and their pages + undrawn span reservation) to the
        arena. ``early``: the tenant exited before its full span — cancel /
        eos / stop-sequence — so the released capacity is *reclaimed*
        headroom the byte budget and join admission see the same tick."""
        arena.free_rows(rows)
        undrawn = arena.reserved_for(rows) if early else 0
        freed = arena.release_row_pages(rows)
        self.metrics.pages_freed += freed
        if early:
            self.metrics.pages_reclaimed += freed + undrawn

    def release(self, arena: CacheArena) -> None:
        """Return a leased arena to the free pool (rows need not be freed
        individually first — a release ends the whole lease). The free pool
        is LRU-capped at ``max_free`` arenas."""
        self._leased.remove(arena)
        self.metrics.pages_freed += arena.release_row_pages(
            list(arena._row_pages))
        arena._free = list(range(arena.batch))
        arena.generation += 1
        self._pooled.append(arena)
        self.metrics.pooled_bytes += arena.nbytes
        if len(self._pooled) > self.max_free:
            self._evict_free(len(self._pooled) - self.max_free)

    # -- the handoff write -------------------------------------------------
    def write_rows(self, arena: CacheArena, rows: Sequence[int],
                   cache: Dict[str, Any],
                   src_rows: Optional[Sequence[int]] = None) -> None:
        """Scatter ``cache`` rows (a prefill-populated *dense* cache at the
        same bucket shape) into ``rows`` of the arena — the prefill→decode
        handoff. Every dense cache leaf is layer-stacked ``(L, B, ...)``,
        so the batch row is axis 1. Rows are fully overwritten, which is
        why recycled arenas need no zeroing on this path. Paged entries
        scatter through the rows' page tables; slots on pages a row never
        committed (beyond its span) hold only zeros in the prefill output
        and are dropped."""
        rows_l = list(rows)
        rows_a = jnp.asarray(rows_l, jnp.int32)
        src_a = jnp.asarray(list(src_rows) if src_rows is not None
                            else list(range(len(rows_l))), jnp.int32)
        if set(cache) != set(arena.cache):
            raise ValueError(
                f"cache keys {sorted(cache)} != arena keys {sorted(arena.cache)}")
        out = {}
        phys, phys_sc = None, -1
        for k, v in arena.cache.items():
            src = jnp.take(cache[k], src_a, axis=1).astype(v.dtype)
            if arena.page and k in arena.paged_keys:
                sc = min(arena.sc, src.shape[2])
                if phys is None or phys_sc != sc:
                    phys = jnp.asarray(
                        arena.phys_slots(rows_l, sc).reshape(-1), jnp.int32)
                    phys_sc = sc
                flat = src[:, :, :sc].reshape(
                    src.shape[0], len(rows_l) * sc, *src.shape[3:])
                out[k] = v.at[:, phys].set(flat, mode="drop")
            else:
                out[k] = v.at[:, rows_a].set(src)
        arena.cache = out
        self.metrics.handoff_writes += 1

    def zero_rows(self, arena: CacheArena, rows: Sequence[int]) -> None:
        """Clear ``rows`` state in place — for tenants without a handoff
        write landing on rows recycled mid-lease (a completed member's
        rows/pages) whose recurrent state would otherwise leak into them."""
        rows_l = list(rows)
        rows_a = jnp.asarray(rows_l, jnp.int32)
        out = {}
        phys = None
        for k, v in arena.cache.items():
            if arena.page and k in arena.paged_keys:
                if phys is None:
                    phys = jnp.asarray(
                        arena.phys_slots(rows_l).reshape(-1), jnp.int32)
                zeros = jnp.zeros((v.shape[0], phys.shape[0], *v.shape[2:]),
                                  v.dtype)
                out[k] = v.at[:, phys].set(zeros, mode="drop")
            else:
                zeros = jnp.zeros((v.shape[0], len(rows_l), *v.shape[2:]),
                                  v.dtype)
                out[k] = v.at[:, rows_a].set(zeros)
        arena.cache = out
