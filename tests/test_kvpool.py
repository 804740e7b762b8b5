"""Host-side page-allocator invariants that previously lived only in
docstrings, now test-gated:

- **writable exclusivity** — no page is ever writable by two slots, and
  any page mapped by several owners (slots/store) is read-only for all
  but its allocator, across admit / grow / share / COW-split / evict
  sequences (``PagePool.check_invariants`` verifies the full ownership
  model: refcount == table references + store pins, free-list
  consistency, single-writer);
- **prefix-trie semantics** — page-granular chain matching (partial
  hits the exact-key store missed), idempotent store, leaf-first LRU
  eviction, COW tails;
- **eviction pressure racing a COW split** — a store entry evicted
  between the trie match and the split must not free the boundary page
  out from under the placement (``map_cow``'s ref holds it).
"""

import numpy as np
import pytest

from kubeflow_tpu.serving.kvpool import (
    OutOfPages,
    PagePool,
    PrefixPageStore,
)


def _pool(pages=16, ps=4, slots=4, per_slot=8):
    return PagePool(pages, ps, slots, per_slot)


def _toks(*vals):
    return np.asarray(vals, np.int32)


def _page_tokens(n_pages, ps=4, base=1):
    return np.arange(base, base + n_pages * ps, dtype=np.int32)


# -- PagePool ownership model ------------------------------------------------


def test_slot_lifecycle_invariants_every_step():
    pool = _pool()
    pool.check_invariants()
    pool.reserve(0, 4)
    pool.check_invariants()
    pool.ensure(0, 9)            # 3 pages for 9 tokens (ps=4)
    pool.check_invariants()
    assert pool.pages_in_use == 3 and pool._slot[0].reserved == 1
    assert all(pool.writer_of(int(p)) == 0
               for p in pool.tables[0, :3])
    pool.ensure(0, 16)           # grow draws the reservation down
    pool.check_invariants()
    with pytest.raises(OutOfPages):
        pool.alloc(0, 5)         # reservation exhausted
    pool.release_slot(0)
    pool.check_invariants()
    pool.check_idle()


def test_shared_pages_are_read_only_for_sharers():
    pool = _pool()
    pool.reserve(0, 2)
    pool.ensure(0, 8)
    store_pages = [pool.pin_one(0, 0), pool.pin_one(0, 1)]
    pool.check_invariants()
    # a second slot maps the shared pages: ref 3, still ONE writer
    pool.reserve(1, 2)
    for logical, p in enumerate(store_pages):
        pool.map_shared(1, logical, p)
    pool.check_invariants()
    assert pool.ref[store_pages[0]] == 3
    assert pool.writer_of(store_pages[0]) == 0
    assert store_pages[0] not in pool._slot[1].owned
    # writer retires: pages survive (store + sharer), no writer at all
    pool.release_slot(0)
    pool.check_invariants()
    assert pool.writer_of(store_pages[0]) is None
    pool.release_slot(1)
    pool.unpin(store_pages)
    pool.check_idle()


def test_cow_split_bookkeeping():
    pool = _pool()
    pool.reserve(0, 1)
    pool.ensure(0, 3)
    boundary = pool.pin_one(0, 0)
    pool.release_slot(0)         # only the store pin remains
    pool.check_invariants()
    pool.reserve(1, 2)           # 1 for the split + 1 to grow
    pool.map_cow(1, 0, boundary)
    pool.check_invariants()
    assert pool.writer_of(boundary) is None      # read-only share
    src, dst = pool.cow_split(1, 0)
    pool.check_invariants()
    assert src == boundary and dst != boundary
    assert pool.writer_of(dst) == 1
    assert pool.tables[1, 0] == dst
    assert pool.ref[boundary] == 1               # back to store-only
    assert pool.cow_splits == 1
    pool.release_slot(1)
    pool.unpin([boundary])
    pool.check_idle()


def test_random_walk_never_double_writes(seed=3):
    """Property walk: random admit/grow/share/COW-split/retire/pin/
    unpin sequences keep the full ownership model intact at every
    step. The deterministic free list makes failures replayable."""
    rng = np.random.default_rng(seed)
    pool = _pool(pages=24, ps=4, slots=6, per_slot=6)
    live = {}       # slot -> tokens grown so far
    pins = []       # store-pinned (page, from_slot)
    cows = {}       # slot -> logical mapped COW
    for step in range(400):
        op = rng.integers(0, 6)
        slot = int(rng.integers(0, 6))
        if op == 0 and slot not in live:           # admit
            need = int(rng.integers(1, 5))
            if pool.can_reserve(need):
                pool.reserve(slot, need)
                live[slot] = 0
        elif op == 1 and slot in live:             # grow
            want = live[slot] + int(rng.integers(1, 8))
            if (pool.pages_needed(want)
                    - pool.pages_needed(live[slot])
                    <= pool._slot[slot].reserved):
                pool.ensure(slot, want)
                live[slot] = want
        elif op == 2 and slot in live and live[slot]:   # store-pin
            logical = int(rng.integers(
                0, pool.pages_needed(live[slot])))
            pins.append(pool.pin_one(slot, logical))
        elif op == 3 and pins and slot not in live:     # COW share
            if pool.can_reserve(1):
                pool.reserve(slot, 1)
                live[slot] = 0
                page = pins[int(rng.integers(0, len(pins)))]
                pool.map_cow(slot, 0, page)
                cows[slot] = 0
        elif op == 4 and slot in cows:             # COW split
            pool.cow_split(slot, cows.pop(slot))
            live[slot] = pool.page_size
        elif op == 5 and slot in live:             # retire
            pool.release_slot(slot)
            live.pop(slot)
            cows.pop(slot, None)
        pool.check_invariants()
    for slot in list(live):
        pool.release_slot(slot)
    pool.unpin(pins)
    pool.check_idle()


# -- PrefixPageStore: the page-granular trie ---------------------------------


def _stored_slot(pool, slot, tokens, prefix_len, store):
    """Simulate an admitted slot whose prompt pages hold ``tokens`` and
    store its prefix — the engine's placement+finalize, pool-side."""
    pool.reserve(slot, pool.pages_needed(tokens.size))
    pool.ensure(slot, tokens.size)
    store.store(tokens, prefix_len, slot)


def test_trie_partial_chain_hit_exact_store_missed():
    """THE trie acceptance shape: the old store keyed on the ENTIRE
    aligned prefix, so a request sharing only the first page(s) of a
    stored prefix shared nothing. The trie matches per page."""
    pool = _pool()
    store = PrefixPageStore(pool, budget_pages=8)
    toks = _page_tokens(3)                    # 12 tokens = 3 pages
    _stored_slot(pool, 0, toks, 12, store)
    assert store.pages_held == 3
    # same first page only — exact-key lookup of (8, bytes) would miss
    other = np.concatenate([toks[:4], _toks(90, 91, 92, 93, 94)])
    m = store.match(other, 8)
    assert m.hit and len(m.pages) == 1
    assert m.pages[0] == int(pool.tables[0, 0])
    # two shared pages out of three stored
    m2 = store.match(np.concatenate([toks[:8], _toks(77, 78, 79, 80)]),
                     12)
    assert len(m2.pages) == 2 and m2.tail_page is None
    # full chain + no tail requested
    m3 = store.match(toks, 12)
    assert len(m3.pages) == 3
    pool.release_slot(0)
    store.clear()
    pool.check_idle()


def test_trie_cow_tail_match_and_store_idempotent():
    pool = _pool()
    store = PrefixPageStore(pool, budget_pages=8)
    toks = _toks(*range(1, 11))               # 10 tokens: 2 pages + 2
    _stored_slot(pool, 0, toks, 10, store)
    assert store.pages_held == 3              # 2 nodes + 1 tail
    m = store.match(np.concatenate([toks[:10], _toks(55)]), 10)
    assert len(m.pages) == 2
    assert m.tail_page == int(pool.tables[0, 2]) and m.tail_len == 2
    # different boundary tokens: full pages hit, tail misses
    m2 = store.match(np.concatenate([toks[:8], _toks(66, 67)]), 10)
    assert len(m2.pages) == 2 and m2.tail_page is None
    # re-store is a pure LRU touch
    store.store(toks, 10, 0)
    assert store.pages_held == 3
    pool.release_slot(0)
    store.clear()
    pool.check_idle()


def test_trie_evicts_leaf_first_lru():
    pool = _pool(pages=16)
    store = PrefixPageStore(pool, budget_pages=4)
    toks = _page_tokens(2)
    _stored_slot(pool, 0, toks, 8, store)       # chain of 2
    branch = np.concatenate([toks[:4], _toks(50, 51, 52, 53, 54)])
    _stored_slot(pool, 1, branch, 9, store)     # +1 node +1 tail
    assert store.pages_held == 4
    root_page = int(pool.tables[0, 0])
    # the shared root is interior (two chains + a tail below): three
    # evictions must remove leaves before it ever becomes evictable
    for _ in range(3):
        assert store.evict_lru()
        held = set(store._held)
        assert root_page in held
        pool.check_invariants()
    assert store.evict_lru()                    # now the root leaf
    assert store.pages_held == 0
    pool.release_slot(0)
    pool.release_slot(1)
    pool.check_idle()


def test_eviction_pressure_racing_cow_split():
    """Placement takes the COW ref BEFORE reservation-driven eviction
    can run (`PagedCache._place` order). Even when the store entry is
    evicted between the match and the split — the eviction-pressure
    race — the boundary page survives on the slot's ref and the split
    copies from live content; afterwards the pool reclaims fully."""
    pool = _pool(pages=6, ps=4, slots=3, per_slot=4)
    store = PrefixPageStore(pool, budget_pages=4)
    toks = _toks(*range(1, 7))                  # 1 page + 2 boundary
    _stored_slot(pool, 0, toks, 6, store)
    pool.release_slot(0)                        # store-only now
    assert store.pages_held == 2
    m = store.match(np.concatenate([toks, _toks(88, 89)]), 6)
    assert len(m.pages) == 1 and m.tail_page is not None
    # placement: reserve, map shared+cow, THEN the store gets evicted
    # under pressure (protect excludes nothing here — worst case)
    pool.reserve(1, 2)
    pool.map_shared(1, 0, m.pages[0])
    pool.map_cow(1, 1, m.tail_page)
    while store.evict_lru():
        pass
    assert store.pages_held == 0
    pool.check_invariants()
    assert pool.ref[m.tail_page] == 1           # the slot's COW ref
    src, dst = pool.cow_split(1, 1)
    pool.check_invariants()
    assert src == m.tail_page and pool.writer_of(dst) == 1
    pool.release_slot(1)
    pool.check_idle()


def test_evict_lru_protect_skips_inflight_share():
    pool = _pool()
    store = PrefixPageStore(pool, budget_pages=8)
    a = _page_tokens(1)
    b = _page_tokens(1, base=60)
    _stored_slot(pool, 0, a, 4, store)
    _stored_slot(pool, 1, b, 4, store)
    protected = int(pool.tables[0, 0])
    assert store.evict_lru(protect={protected})
    assert protected in store._held             # the OTHER entry went
    assert not store.evict_lru(protect={protected})
    pool.release_slot(0)
    pool.release_slot(1)
    store.clear()
    pool.check_idle()


def test_store_respects_budget_and_zero_budget():
    pool = _pool(pages=16)
    disabled = PrefixPageStore(pool, budget_pages=0)
    pool.reserve(0, 3)
    pool.ensure(0, 12)
    disabled.store(_page_tokens(3), 12, 0)
    assert disabled.pages_held == 0
    small = PrefixPageStore(pool, budget_pages=2)
    small.store(_page_tokens(3), 12, 0)         # truncates at budget
    assert small.pages_held == 2
    assert len(small.match(_page_tokens(3), 12).pages) == 2
    pool.release_slot(0)
    small.clear()
    pool.check_idle()
