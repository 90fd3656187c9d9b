"""Tree-backed device engines: the O(log N) automata of the paper.

The dense automata in :mod:`repro.cachesim.engines` pay O(C) vector work
per request (slot-wide compares and argmins) and the fractional replay pays
O(N) per chunk.  This module re-implements the eviction machinery on the
packed radix trees of :mod:`repro.kernels.prefix_tree`, turning the
per-request cost into O(R log_R ·) scatter/gather paths while staying
**bit-exact** against the dense steps (the differential tests in
``tests/cachesim/test_tree_policies.py`` compare hit sequences request by
request).

Three engines:

* **tree-LRU** — chunk-batched *reuse distance*: a request hits iff the
  number of distinct items since its previous occurrence is at most C-1,
  which is exactly LRU.  Marks (last occurrences) live on a ring of
  positions with a radix-16 count tree over them; a chunk of W requests is
  resolved with two batched prefix queries plus a (W, W) in-chunk dominance
  term, and the tree moves each distinct item's mark once per chunk.  When
  the ring fills, a rank-compaction keeps only the newest ``capacity``
  marks — exact, because a reuse window reaching past those marks already
  contains >= capacity distinct items (a certain miss either way), and
  dropped items re-enter as first-seen misses, which they would be.
* **tree-LFU / tree-FTPL** — per-request automata whose victim search is a
  lexicographic (hi, lo) min-tree over slots: (frequency, tick) for LFU,
  (sortable perturbed score, item id) for FTPL — the same eviction keys and
  tie-breaks as the dense steps, so hit sequences agree bit for bit.  All
  writes are *delayed* one request (applied at the start of the next step)
  so no gather reads a just-scattered array — the anti-dependency would
  otherwise force a full-array copy per request.

Per-chunk steps keep their pending writes in the **inner** scan carry and
flush them before returning, so the outer carry is window-independent —
the streaming/resume contract of :mod:`repro.cachesim.api` (two chunked
runs replay one full run bit for bit) holds for any window split.

FIFO stays dense: its eviction order is insertion time, which reuse
distances cannot express, and its O(C) step is already cheap.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.ftpl import ftpl_initial_top_c, ftpl_noise, theoretical_zeta
from repro.kernels.prefix_tree import ops as pt

_I32_MAX = np.int32(np.iinfo(np.int32).max)

#: kinds with a tree-backed implementation (impl="tree" in the API layer)
TREE_ENGINE_KINDS = ("lru", "lfu", "ftpl")

#: radix of the position tree (LRU ring) — 16 lanes keep the sibling
#: gathers one vector register wide while the ring tree stays 4 levels deep
RING_RADIX = 16
#: radix of the slot min-trees (LFU/FTPL) — 64-wide groups make catalogs of
#: thousands of slots two levels deep
SLOT_RADIX = 64
#: sub-chunk width cap for the reuse-distance engine: the (W, W) in-chunk
#: dominance term is brute-force, and past ~128 it stops being free
MAX_SUBCHUNK = 128


# ---------------------------------------------------------------------------
# carries
# ---------------------------------------------------------------------------
class TreeLRUCarry(NamedTuple):
    """Reuse-distance LRU state (window-independent; pends live inner-scan)."""

    tree: jax.Array  # (TOT,) int32 packed radix-16 mark-count tree
    last: jax.Array  # (N+1,) int32 item -> ring position of last occurrence
    pos: jax.Array  # () int32 next free ring position
    nseen: jax.Array  # () int32 distinct items seen (occupancy = min(, cap))
    cap: jax.Array  # () int32 capacity (traced: sweeps stack it)


class TreeLFUCarry(NamedTuple):
    imap: jax.Array  # (N+1,) int32 item -> slot (-1 out; N is scratch)
    counts: jax.Array  # (N,) int32 perfect-LFU counters
    slots: jax.Array  # (K,) int32 slot -> item (-1 empty, -2 inactive)
    tree_hi: jax.Array  # (TOT,) int32 min-tree over slot frequencies
    tree_lo: jax.Array  # (TOT,) int32 min-tree over slot ticks
    t: jax.Array  # () int32


class TreeFTPLCarry(NamedTuple):
    imap: jax.Array  # (N+1,) int32 item -> slot (-1 out; N is scratch)
    counts: jax.Array  # (N,) int32 request counters
    noise: jax.Array  # (N,) float32 one-shot perturbation (constant)
    slots: jax.Array  # (K,) int32 slot -> item (-2 inactive)
    tree_hi: jax.Array  # (TOT,) int32 min-tree over sortable scores
    tree_lo: jax.Array  # (TOT,) int32 min-tree over slot item ids


# ---------------------------------------------------------------------------
# geometry helpers
# ---------------------------------------------------------------------------
def ring_size(n_slots: int) -> int:
    """Ring length: power of two with >= 4x slack over the kept-mark count
    (compaction keeps at most ``capacity`` marks).  The floor is generous —
    each compaction pays an argsort over the catalog, so headroom buys
    throughput directly (8192 vs 65536 measured 2x on the bench trace) and
    the tree costs only ~level-sum(m) int32s."""
    m = 65536
    while m < 4 * int(n_slots):
        m *= 2
    return m


def _pick_subchunk(window: int) -> int:
    """Largest divisor of ``window`` that is <= MAX_SUBCHUNK, preferring
    16-aligned widths (aligned sub-chunks take the cheap grouped-insert
    path: ~2x fewer scatter elements per request)."""
    best, best_aligned = 1, 1
    for d in range(1, min(window, MAX_SUBCHUNK) + 1):
        if window % d == 0:
            best = d
            if d % RING_RADIX == 0:
                best_aligned = d
    return best_aligned if best_aligned > 1 else best


# ---------------------------------------------------------------------------
# tree-LRU: chunk-batched reuse distance
# ---------------------------------------------------------------------------
def init_tree_lru_carry(catalog_size: int, capacity: int,
                        n_slots: Optional[int] = None,
                        ring: Optional[int] = None) -> TreeLRUCarry:
    k = int(n_slots) if n_slots else int(capacity)
    m = int(ring) if ring else ring_size(k)
    if m & (m - 1) or m < 4 * k:
        raise ValueError(
            f"ring must be a power of two >= 4 * n_slots, got {m} for {k}"
        )
    return TreeLRUCarry(
        tree=jnp.zeros(pt.tree_storage(m, RING_RADIX), jnp.int32),
        last=jnp.full(catalog_size + 1, -1, jnp.int32),
        pos=jnp.zeros((), jnp.int32),
        nseen=jnp.zeros((), jnp.int32),
        cap=jnp.int32(capacity),
    )


@functools.lru_cache(maxsize=None)
def make_lru_tree_chunk(catalog_size: int, m: int,
                        return_flags: bool = False):
    """Chunk step ``(carry, ids(window,)) -> (carry, (hits, occ))`` for the
    reuse-distance engine; the sub-chunk width W is derived from the traced
    chunk shape, so one factory serves every window.  ``return_flags=True``
    replaces the hit count with the (window,) per-request flags (the inner
    scan's (n_sub, w) flag rows, flattened back to request order)."""
    radix = RING_RADIX
    sh = radix.bit_length() - 1
    offs = pt.tree_offsets(m, radix)
    nlev = len(offs)

    def compact(tree, last, pos, cap):
        # rank-remap marks to [0, kept); drop all but the newest `cap`
        # marks (exact: a reuse window reaching past them holds >= cap
        # marks, a certain miss, and dropped items re-enter as first-seen)
        nmarks = jnp.sum(last >= 0, dtype=jnp.int32)
        kept = jnp.minimum(nmarks, cap)
        key = jnp.where(last >= 0, last, _I32_MAX)
        order = jnp.argsort(key)
        ranks = jnp.zeros_like(key).at[order].set(
            jnp.arange(key.shape[0], dtype=jnp.int32)
        )
        newrank = ranks - (nmarks - kept)
        newlast = jnp.where((last >= 0) & (newrank >= 0), newrank, -1)
        leaf = (jnp.arange(m, dtype=jnp.int32) < kept).astype(jnp.int32)
        tree = pt.tree_build(leaf, radix)
        npos = (kept + radix - 1) & ~(radix - 1)  # 16-aligned restart
        return tree, newlast, npos

    def chunk(carry, ids):
        window = ids.shape[0]
        # compaction runs (at most) once per *chunk*, not per sub-chunk:
        # after it, pos <= aligned(cap) <= m/4 + 16, so a whole window of
        # inserts fits.  Keeping the cond out of the inner scan matters
        # under vmap (sweeps), where a batched cond executes both branches
        # — per sub-chunk that would pay the argsort every step.
        if window > 3 * (m // 4) - RING_RADIX:
            raise ValueError(
                f"window {window} too large for ring {m}; pass a larger "
                f"ring= to init (need window <= 3*ring/4 - {RING_RADIX})"
            )
        w = _pick_subchunk(window)
        aligned = w % radix == 0
        if aligned:
            npend = w * nlev + w + (nlev - 1) * (w // radix)
        else:
            npend = w * nlev * 2
        eye = jnp.eye(w, dtype=bool)
        lanes = jnp.arange(w, dtype=jnp.int32)

        def substep(st, sub_ids):
            tree, last, pos, nseen, cap, pn, pd, pli, plv = st
            # delayed writes: apply the previous sub-chunk's tree deltas
            # and mark moves before reading anything
            tree = tree.at[pn].add(pd)
            last = last.at[pli].max(plv)
            kpos = pos + lanes
            lastg = last[sub_ids]
            eq = sub_ids[None, :] == sub_ids[:, None]
            lower = kpos[None, :] < kpos[:, None]
            prev_in = jnp.max(jnp.where(eq & lower, kpos[None, :], -1), axis=1)
            prevp = jnp.where(prev_in >= 0, prev_in, lastg)
            islast = ~jnp.any(eq & ~lower & ~eye, axis=1)
            # d(i) = tree marks in (prev(i), chunk start) + in-chunk firsts
            # in (prev(i), i) — the dominance term, brute (W, W)
            base = pt.tree_prefix(
                tree, m, radix, jnp.full((1,), pos - 1, jnp.int32)
            )[0]
            dpre = base - pt.tree_prefix(
                tree, m, radix, jnp.minimum(prevp, pos - 1)
            )
            dom = (
                (prevp[None, :] <= prevp[:, None])
                & (kpos[None, :] > prevp[:, None])
                & lower
            )
            d = dpre + jnp.sum(dom, axis=1, dtype=jnp.int32)
            hit = (prevp >= 0) & (d <= cap - 1)
            nseen = nseen + jnp.sum(prevp < 0, dtype=jnp.int32)

            # plan next sub-chunk's writes: remove pre-chunk marks that
            # moved, insert marks at last in-chunk occurrences
            rm = jnp.where((lastg >= 0) & (prev_in < 0), lastg, -1)
            rm_nodes, rm_deltas, node = [], [], rm
            for l in range(nlev):
                ok = rm >= 0
                rm_nodes.append(jnp.where(ok, offs[l] + node, 0))
                rm_deltas.append(jnp.where(ok, jnp.int32(-1), 0))
                node = node >> sh
            ins = islast.astype(jnp.int32)
            if aligned:
                # leaf groups are complete (pos and W both 16-aligned):
                # exact level-1 deltas via reshape; higher levels scatter
                # the same (W/16,) deltas at ancestor nodes (duplicate
                # indices accumulate across group boundaries)
                g1 = ins.reshape(-1, radix).sum(1, dtype=jnp.int32)
                gids = (pos >> sh) + jnp.arange(g1.shape[0], dtype=jnp.int32)
                node = gids
                ins_nodes, ins_deltas = [], []
                for l in range(1, nlev):
                    ins_nodes.append(offs[l] + node)
                    ins_deltas.append(g1)
                    node = node >> sh
                pn = jnp.concatenate([*rm_nodes, kpos] + ins_nodes)
                pd = jnp.concatenate([*rm_deltas, ins] + ins_deltas)
            else:
                node = kpos
                ins_nodes, ins_deltas = [], []
                for l in range(nlev):
                    ins_nodes.append(offs[l] + node)
                    ins_deltas.append(ins)
                    node = node >> sh
                pn = jnp.concatenate(rm_nodes + ins_nodes)
                pd = jnp.concatenate(rm_deltas + ins_deltas)
            pli, plv = sub_ids, kpos
            st = (tree, last, pos + w, nseen, cap, pn, pd, pli, plv)
            return st, hit

        tree, last, pos = jax.lax.cond(
            carry.pos + window > m,
            lambda a: compact(a[0], a[1], a[2], carry.cap),
            lambda a: a,
            (carry.tree, carry.last, carry.pos),
        )
        # pend arrays are inner-scan state only, flushed before returning,
        # so the outer carry does not depend on the window split
        st = (
            tree, last, pos, carry.nseen, carry.cap,
            jnp.zeros(npend, jnp.int32), jnp.zeros(npend, jnp.int32),
            jnp.zeros(w, jnp.int32), jnp.full(w, -1, jnp.int32),
        )
        st, hits = jax.lax.scan(substep, st, ids.reshape(-1, w))
        tree, last, pos, nseen, cap, pn, pd, pli, plv = st
        tree = tree.at[pn].add(pd)
        last = last.at[pli].max(plv)
        out = TreeLRUCarry(tree, last, pos, nseen, cap)
        if return_flags:
            return out, (hits.reshape(-1), jnp.minimum(nseen, cap))
        nhits = jnp.sum(hits.astype(jnp.int32))
        return out, (nhits, jnp.minimum(nseen, cap))

    return chunk


# ---------------------------------------------------------------------------
# tree-LFU / tree-FTPL: delayed-write min-pair automata
# ---------------------------------------------------------------------------
def _slot_tot(k: int) -> int:
    return pt.tree_storage(k, SLOT_RADIX)


def init_tree_lfu_carry(catalog_size: int, capacity: int,
                        n_slots: Optional[int] = None) -> TreeLFUCarry:
    k = int(n_slots) if n_slots else int(capacity)
    c = int(capacity)
    hi = np.full(k, _I32_MAX, np.int32)
    lo = np.full(k, _I32_MAX, np.int32)
    hi[:c] = -1  # empty slots: freq -1 sorts below any real frequency
    lo[:c] = -1
    th, tl = pt.minpair_build(jnp.asarray(hi), jnp.asarray(lo), SLOT_RADIX)
    slots = np.full(k, -2, np.int32)
    slots[:c] = -1
    return TreeLFUCarry(
        imap=jnp.full(catalog_size + 1, -1, jnp.int32),
        counts=jnp.zeros(catalog_size, jnp.int32),
        slots=jnp.asarray(slots),
        tree_hi=th,
        tree_lo=tl,
        t=jnp.zeros((), jnp.int32),
    )


def init_tree_ftpl_carry(catalog_size: int, capacity: int,
                         n_slots: Optional[int] = None, *, seed: int = 0,
                         zeta: Optional[float] = None,
                         horizon: Optional[int] = None) -> TreeFTPLCarry:
    k = int(n_slots) if n_slots else int(capacity)
    c = int(capacity)
    if zeta is None:
        if horizon is None:
            raise ValueError("ftpl needs zeta or horizon")
        zeta = theoretical_zeta(c, catalog_size, horizon)
    noise = ftpl_noise(catalog_size, zeta, seed=seed)
    top = ftpl_initial_top_c(noise, c).astype(np.int32)
    slots = np.full(k, -2, np.int32)
    slots[:c] = top
    imap = np.full(catalog_size + 1, -1, np.int32)
    imap[top] = np.arange(c, dtype=np.int32)
    hi = np.full(k, _I32_MAX, np.int32)
    lo = np.full(k, _I32_MAX, np.int32)
    hi[:c] = np.asarray(
        pt.sortable_f32(jnp.asarray(noise[top], jnp.float32))
    )
    lo[:c] = top
    th, tl = pt.minpair_build(jnp.asarray(hi), jnp.asarray(lo), SLOT_RADIX)
    return TreeFTPLCarry(
        imap=jnp.asarray(imap),
        counts=jnp.zeros(catalog_size, jnp.int32),
        noise=jnp.asarray(noise),
        slots=jnp.asarray(slots),
        tree_hi=th,
        tree_lo=tl,
    )


def _wrap_pend_chunk(substep, pack, unpack, return_flags: bool = False):
    """Build ``chunk(carry, ids)`` from a delayed-write per-request substep:
    pending writes ride the inner carry and are flushed before returning.
    ``return_flags=True`` emits the per-request hit flags instead of their
    sum (the sized runs weight each hit by the requested item's bytes)."""

    def chunk(carry, ids):
        st = pack(carry)
        st, hits = jax.lax.scan(substep, st, ids)
        carry = unpack(st)
        if return_flags:
            return carry, hits
        return carry, jnp.sum(hits.astype(jnp.int32))

    return chunk


@functools.lru_cache(maxsize=None)
def make_lfu_tree_chunk(catalog_size: int, k: int,
                        return_flags: bool = False):
    n = catalog_size
    radix = SLOT_RADIX
    offs = pt.tree_offsets(k, radix)

    def substep(st, j):
        (imap, counts, slots, th, tl, t,
         pci, pcd, pii, piv, psi, psv, pti, pth, ptl) = st
        counts = counts.at[pci].add(pcd)
        imap = imap.at[pii].set(piv)
        slots = slots.at[psi].set(psv)
        th = th.at[pti].set(pth)
        tl = tl.at[pti].set(ptl)

        slot = imap[j]
        hit = slot >= 0
        f = counts[j] + 1  # the dense step increments before keying
        root_hi, _ = pt.minpair_root(th, tl, k, radix)
        victim = pt.minpair_argmin(th, tl, k, radix).astype(jnp.int32)
        idx = jnp.where(hit, slot, victim)
        # admission: the newcomer must match the victim's frequency
        write = jnp.logical_or(hit, f >= root_hi)
        old = slots[idx]
        new_hi = jnp.where(write, f, th[idx])  # no-op plan when not writing
        new_lo = jnp.where(write, t, tl[idx])
        pti, pth, ptl = pt.minpair_update_plan(th, tl, k, radix, idx,
                                               new_hi, new_lo)
        pci, pcd = j, jnp.int32(1)
        psi = idx
        psv = jnp.where(write, j, old)
        mo = jnp.where(write & (old >= 0) & (old != j), old, n)  # n: scratch
        mj = jnp.where(write, j, n)
        pii = jnp.stack([mo, mj])
        piv = jnp.stack([jnp.int32(-1), idx])
        st = (imap, counts, slots, th, tl, t + 1,
              pci, pcd, pii, piv, psi, psv, pti, pth, ptl)
        return st, hit

    def pack(c: TreeLFUCarry):
        return (
            c.imap, c.counts, c.slots, c.tree_hi, c.tree_lo, c.t,
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.full(2, n, jnp.int32), jnp.full(2, -1, jnp.int32),
            jnp.zeros((), jnp.int32), c.slots[0],
            jnp.asarray(offs, jnp.int32), c.tree_hi[jnp.asarray(offs)],
            c.tree_lo[jnp.asarray(offs)],
        )

    def unpack(st):
        (imap, counts, slots, th, tl, t,
         pci, pcd, pii, piv, psi, psv, pti, pth, ptl) = st
        counts = counts.at[pci].add(pcd)
        imap = imap.at[pii].set(piv)
        slots = slots.at[psi].set(psv)
        th = th.at[pti].set(pth)
        tl = tl.at[pti].set(ptl)
        return TreeLFUCarry(imap, counts, slots, th, tl, t)

    return _wrap_pend_chunk(substep, pack, unpack, return_flags)


@functools.lru_cache(maxsize=None)
def make_ftpl_tree_chunk(catalog_size: int, k: int,
                         return_flags: bool = False):
    n = catalog_size
    radix = SLOT_RADIX
    offs = pt.tree_offsets(k, radix)

    def substep(st, j):
        (imap, counts, noise, slots, th, tl,
         pci, pcd, pii, piv, psi, psv, pti, pth, ptl) = st
        counts = counts.at[pci].add(pcd)
        imap = imap.at[pii].set(piv)
        slots = slots.at[psi].set(psv)
        th = th.at[pti].set(pth)
        tl = tl.at[pti].set(ptl)

        slot = imap[j]
        hit = slot >= 0
        s = (counts[j] + 1).astype(jnp.float32) + noise[j]
        skey = pt.sortable_f32(s)
        root_hi, _ = pt.minpair_root(th, tl, k, radix)
        victim = pt.minpair_argmin(th, tl, k, radix).astype(jnp.int32)
        # strict >, like the dense step; sortable_f32 preserves float order
        swap = jnp.logical_and(~hit, skey > root_hi)
        idx = jnp.where(hit, slot, victim)
        upd = jnp.logical_or(hit, swap)  # a hit refreshes its slot's score
        old = slots[idx]
        new_hi = jnp.where(upd, skey, th[idx])
        new_lo = jnp.where(upd, j, tl[idx])
        pti, pth, ptl = pt.minpair_update_plan(th, tl, k, radix, idx,
                                               new_hi, new_lo)
        pci, pcd = j, jnp.int32(1)
        psi = idx
        psv = jnp.where(upd, j, old)
        mo = jnp.where(swap & (old >= 0), old, n)  # n: scratch index
        mj = jnp.where(swap, j, n)
        pii = jnp.stack([mo, mj])
        piv = jnp.stack([jnp.int32(-1), idx])
        st = (imap, counts, noise, slots, th, tl,
              pci, pcd, pii, piv, psi, psv, pti, pth, ptl)
        return st, hit

    def pack(c: TreeFTPLCarry):
        return (
            c.imap, c.counts, c.noise, c.slots, c.tree_hi, c.tree_lo,
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.full(2, n, jnp.int32), jnp.full(2, -1, jnp.int32),
            jnp.zeros((), jnp.int32), c.slots[0],
            jnp.asarray(offs, jnp.int32), c.tree_hi[jnp.asarray(offs)],
            c.tree_lo[jnp.asarray(offs)],
        )

    def unpack(st):
        (imap, counts, noise, slots, th, tl,
         pci, pcd, pii, piv, psi, psv, pti, pth, ptl) = st
        counts = counts.at[pci].add(pcd)
        imap = imap.at[pii].set(piv)
        slots = slots.at[psi].set(psv)
        th = th.at[pti].set(pth)
        tl = tl.at[pti].set(ptl)
        return TreeFTPLCarry(imap, counts, noise, slots, th, tl)

    return _wrap_pend_chunk(substep, pack, unpack, return_flags)


# ---------------------------------------------------------------------------
# tree-GDS: GreedyDual-Size on the min-pair eviction trees
# ---------------------------------------------------------------------------
class TreeGDSCarry(NamedTuple):
    """GreedyDual-Size (Cao & Irani 1997) automaton state.

    Size-normalized eviction keys: every resident item carries a priority
    H_i = L + cost_i / size_i where L is the global inflation value (the
    last evicted item's H), so small/costly objects survive longer.  The
    victim search is the same lexicographic min-pair tree as LFU/FTPL with
    (sortable H, item id) keys — the id tie-break matches the host oracle's
    sorted-store ``(key, item)`` ordering.  Capacity is slot-based (like
    the host ``core.policies.GDS``); sizes shape the *priorities* and the
    byte-hit accounting, not the occupancy constraint.
    """

    imap: jax.Array  # (N+1,) int32 item -> slot (-1 out; N is scratch)
    hval: jax.Array  # (K,) float32 slot -> current H (reads L back as float)
    L: jax.Array  # () float32 global inflation value
    prio: jax.Array  # (N,) float32 per-item cost_i / size_i increments
    szs: jax.Array  # (N,) float32 per-item sizes (byte accounting; 1 = unit)
    slots: jax.Array  # (K,) int32 slot -> item (-1 empty, -2 inactive)
    tree_hi: jax.Array  # (TOT,) int32 min-tree over sortable H
    tree_lo: jax.Array  # (TOT,) int32 min-tree over slot item ids


def init_tree_gds_carry(
    catalog_size: int,
    capacity: int,
    n_slots: Optional[int] = None,
    *,
    sizes: Optional[np.ndarray] = None,
    costs: Optional[np.ndarray] = None,
) -> TreeGDSCarry:
    n = int(catalog_size)
    k = int(n_slots) if n_slots else int(capacity)
    c = int(capacity)
    s = np.ones(n, np.float32) if sizes is None else np.asarray(
        sizes, np.float32
    )
    w = np.ones(n, np.float32) if costs is None else np.asarray(
        costs, np.float32
    )
    if s.shape != (n,) or w.shape != (n,):
        raise ValueError(f"sizes/costs must be ({n},) arrays")
    if not (np.all(np.isfinite(s)) and s.min() > 0.0):
        raise ValueError("gds sizes must be finite and > 0")
    if not (np.all(np.isfinite(w)) and w.min() > 0.0):
        raise ValueError("gds costs must be finite and > 0")
    hi = np.full(k, _I32_MAX, np.int32)
    lo = np.full(k, _I32_MAX, np.int32)
    hi[:c] = -1  # empty slots sort below any real H (sortable(H>0) > 0)
    lo[:c] = -1
    th, tl = pt.minpair_build(jnp.asarray(hi), jnp.asarray(lo), SLOT_RADIX)
    slots = np.full(k, -2, np.int32)
    slots[:c] = -1
    return TreeGDSCarry(
        imap=jnp.full(n + 1, -1, jnp.int32),
        hval=jnp.zeros(k, jnp.float32),
        L=jnp.zeros((), jnp.float32),
        prio=jnp.asarray(w / s),
        szs=jnp.asarray(s),
        slots=jnp.asarray(slots),
        tree_hi=th,
        tree_lo=tl,
    )


@functools.lru_cache(maxsize=None)
def make_gds_tree_chunk(catalog_size: int, k: int,
                        return_flags: bool = False):
    n = catalog_size
    radix = SLOT_RADIX
    offs = pt.tree_offsets(k, radix)

    def substep(st, j):
        (imap, hval, L, prio, szs, slots, th, tl,
         pii, piv, psi, psv, phi, phv, pti, pth, ptl) = st
        imap = imap.at[pii].set(piv)
        slots = slots.at[psi].set(psv)
        hval = hval.at[phi].set(phv)
        th = th.at[pti].set(pth)
        tl = tl.at[pti].set(ptl)

        slot = imap[j]
        hit = slot >= 0
        victim = pt.minpair_argmin(th, tl, k, radix).astype(jnp.int32)
        idx = jnp.where(hit, slot, victim)
        old = slots[idx]
        # host order: evict first (L <- H_min of a *real* victim), then
        # key the newcomer off the updated L.  Empty-slot fills and hits
        # leave L unchanged.
        evict = jnp.logical_and(~hit, old >= 0)
        L = jnp.where(evict, hval[idx], L)
        h = L + prio[j]
        pti, pth, ptl = pt.minpair_update_plan(
            th, tl, k, radix, idx, pt.sortable_f32(h), j
        )
        psi, psv = idx, j
        phi, phv = idx, h
        mo = jnp.where(evict, old, n)  # n: scratch index
        pii = jnp.stack([mo, j])
        piv = jnp.stack([jnp.int32(-1), idx])
        st = (imap, hval, L, prio, szs, slots, th, tl,
              pii, piv, psi, psv, phi, phv, pti, pth, ptl)
        return st, hit

    def pack(c: TreeGDSCarry):
        return (
            c.imap, c.hval, c.L, c.prio, c.szs, c.slots,
            c.tree_hi, c.tree_lo,
            jnp.full(2, n, jnp.int32), jnp.full(2, -1, jnp.int32),
            jnp.zeros((), jnp.int32), c.slots[0],
            jnp.zeros((), jnp.int32), c.hval[0],
            jnp.asarray(offs, jnp.int32), c.tree_hi[jnp.asarray(offs)],
            c.tree_lo[jnp.asarray(offs)],
        )

    def unpack(st):
        (imap, hval, L, prio, szs, slots, th, tl,
         pii, piv, psi, psv, phi, phv, pti, pth, ptl) = st
        imap = imap.at[pii].set(piv)
        slots = slots.at[psi].set(psv)
        hval = hval.at[phi].set(phv)
        th = th.at[pti].set(pth)
        tl = tl.at[pti].set(ptl)
        return TreeGDSCarry(imap, hval, L, prio, szs, slots, th, tl)

    return _wrap_pend_chunk(substep, pack, unpack, return_flags)


# ---------------------------------------------------------------------------
# lazy bucketized OGB: O(B log V) per chunk, independent of the catalog size
# ---------------------------------------------------------------------------
#: bucket count of the value histogram the lazy projection solves over
OGB_TREE_BUCKETS = 65536
#: radix of the bucket count/sum trees
OGB_TREE_RADIX = 64
#: halvings of the per-chunk threshold solve's bracket (its resolution is
#: the bracket over 2**OGB_TREE_ITERS); each round resolves log2 of
#: OGB_TREE_SPLIT of them
OGB_TREE_ITERS = 30
#: sub-intervals per round of the threshold solve: a round evaluates the
#: mass at all OGB_TREE_SPLIT - 1 interior points at once, so 30 halvings
#: take six dependent rounds
OGB_TREE_SPLIT = 32
#: grid headroom factor: the value grid spans ~2*GAIN chunk-updates of rho
#: growth before a re-anchor pass is needed
OGB_TREE_GAIN = 8.0


class OGBTreeCarry(NamedTuple):
    """Lazy OGB state: absolute accumulated values + cumulative threshold.

    The dense replay projects the whole catalog every chunk.  Here the
    state is the *unprojected* accumulation ``y`` with ``f = clip(y - rho,
    0, 1)`` implicit, and the per-chunk projection becomes a scalar solve
    of ``mass(rho) = sum_b cnt_b * clip(mean_b - rho, 0, 1) = C`` over a
    V-bucket histogram of ``y`` kept in packed radix trees — the chunk
    touches O(B log V) tree nodes, never the catalog.
    """

    y: jax.Array  # (N,) float32 accumulated values (f = clip(y - rho, 0, 1))
    rho: jax.Array  # () float32 cumulative projection threshold
    eta: jax.Array  # () float32
    cap: jax.Array  # () float32
    p: jax.Array  # (N,) float32 permanent random numbers, or (0,)
    w: jax.Array  # () float32 bucket width of the value grid
    scratch: jax.Array  # (N,) int32 first-occurrence dedup scratch (I32_MAX)
    ycnt: jax.Array  # (TOT,) float32 bucket-count tree over y
    ysum: jax.Array  # (TOT,) float32 bucket-sum tree over y
    dcnt: jax.Array  # (TOT,) float32 bucket-count tree over y - p, or (0,)


def _ogb_bucket(x, wv, v: int):
    """Grid bucket of value ``x``: the grid covers [-1, v*w - 1) so both y
    (>= 0) and y - p (> -1) share it."""
    b = jnp.floor((x + 1.0) / wv).astype(jnp.int32)
    return jnp.clip(b, 0, v - 1)


def init_ogb_tree_carry(
    catalog_size: int,
    capacity: int,
    *,
    eta: float,
    seed: int = 0,
    sample: str = "poisson",
    buckets: int = OGB_TREE_BUCKETS,
    radix: int = OGB_TREE_RADIX,
    batch_hint: int = 4096,
) -> OGBTreeCarry:
    """Initial carry at the uniform feasible state f = C/N.

    ``batch_hint`` sizes the value grid: headroom for ~2*OGB_TREE_GAIN
    chunks of worst-case rho growth (eta*B per chunk) between re-anchor
    passes.  A larger actual window than the hint is still correct — the
    re-anchor trigger watches the real chunk size — it just re-anchors
    more often."""
    from repro.cachesim.engines import sampling_keys

    n, v = int(catalog_size), int(buckets)
    span = 1.0 + 2.0 * OGB_TREE_GAIN * max(1.0, float(eta) * batch_hint)
    wv = (span + 1.0) / v
    y0 = float(capacity) / n
    p, _ = sampling_keys(seed, n, sample)
    b0 = int(np.clip(np.floor((y0 + 1.0) / wv), 0, v - 1))
    cnt_leaf = np.zeros(v, np.float32)
    cnt_leaf[b0] = n
    sum_leaf = np.zeros(v, np.float32)
    sum_leaf[b0] = n * y0
    ycnt = pt.tree_build(jnp.asarray(cnt_leaf), radix)
    ysum = pt.tree_build(jnp.asarray(sum_leaf), radix)
    if sample == "poisson":
        d0 = y0 - np.asarray(p, np.float64)
        db = np.clip(np.floor((d0 + 1.0) / wv), 0, v - 1).astype(np.int64)
        dcnt = pt.tree_build(
            jnp.asarray(np.bincount(db, minlength=v), jnp.float32), radix
        )
    else:
        dcnt = jnp.zeros((0,), jnp.float32)
    return OGBTreeCarry(
        y=jnp.full(n, y0, jnp.float32),
        rho=jnp.zeros((), jnp.float32),
        eta=jnp.float32(eta),
        cap=jnp.float32(capacity),
        p=p,
        w=jnp.float32(wv),
        scratch=jnp.full(n, _I32_MAX, jnp.int32),
        ycnt=ycnt,
        ysum=ysum,
        dcnt=dcnt,
    )


def _sibling_rows(ycnt, ysum, v: int, radix: int):
    """Each level of the count and sum trees as rows of sibling groups,
    ``(groups, 2 * radix)``: a group of the count tree beside the same
    group of the sum tree, so one row gather per level reads both."""
    rows = []
    for off, size in zip(pt.tree_offsets(v, radix), pt.tree_sizes(v, radix)):
        groups = -(-size // radix)
        pad = (0, groups * radix - size)
        rows.append(jnp.concatenate(
            [jnp.pad(tree[off:off + size], pad).reshape(groups, radix)
             for tree in (ycnt, ysum)], axis=1))
    return rows


def _ogb_tree_mass(rows, total, wv, t, v: int, radix: int):
    """sum_b cnt_b * clip(mean_b - t, 0, 1) at each threshold of ``t``
    (any shape) via O(log V) tree reads.

    The prefix sums are :func:`repro.kernels.prefix_tree.ops.tree_prefix`'s
    (per level, the query ancestor's sibling group masked to its left
    part), but each group is read as one row of ``rows``
    (:func:`_sibling_rows`) rather than as ``radix`` scalar gathers: on
    the TPU a gather costs about the same per row as per element."""
    sh = radix.bit_length() - 1
    lane = jnp.arange(radix, dtype=jnp.int32)
    k0, k1 = _ogb_bucket(t, wv, v), _ogb_bucket(t + 1.0, wv, v)
    node = jnp.stack([k0, k1])
    q = None
    for lvl, r in enumerate(rows):
        grp = r[0] if r.shape[0] == 1 else r[node >> sh]
        vals = grp.reshape(grp.shape[:-1] + (2, radix))
        lim = (node & (radix - 1))[..., None, None]
        if lvl == 0:
            # the bucket itself: (count, sum) of leaves k0 and k1
            leaf = jnp.sum(jnp.where(lane == lim, vals, 0.0), axis=-1)
        within = lane <= lim if lvl == 0 else lane < lim
        part = jnp.sum(jnp.where(within, vals, 0.0), axis=-1)
        q = part if q is None else q + part
        node = node >> sh
    qc, qs = q[..., 0], q[..., 1]
    cb, sb = leaf[..., 0], leaf[..., 1]
    # buckets above k1 are entirely past t+1: full mass
    above = total - qc[1]
    # buckets strictly between k0 and k1 lie in the linear clip region
    mid_c = qc[1] - cb[1] - qc[0]
    mid_s = qs[1] - sb[1] - qs[0]
    mid = mid_s - t * mid_c
    # boundary buckets: mean-clip approximation
    mean = jnp.where(cb > 0, sb / jnp.maximum(cb, 1.0), 0.0)
    bnd = cb * jnp.clip(mean - t, 0.0, 1.0)
    return above + mid + bnd[0] + jnp.where(k1 > k0, bnd[1], 0.0)


def _ogb_tree_solve(ycnt, ysum, wv, total, cap, lo, hi, v: int, radix: int,
                    iters: int):
    """Threshold solve on the bracket ``[lo, hi]`` with ``mass(lo) >= cap``:
    the left end of the final bracket after ``iters`` halvings.

    A K-ary search, K = OGB_TREE_SPLIT: each round evaluates the mass at
    the K - 1 interior points of the bracket in one batched tree read and
    keeps the sub-interval where ``mass >= cap`` first fails, so it
    resolves log2(K) halvings; the last round is narrower when ``iters``
    is not a multiple of log2(K).  For a monotone mass this is the result
    of ``iters`` bisection steps in exact arithmetic.  The rounds are
    unrolled in Python, so the chunk holds no device loop.
    """
    rows = _sibling_rows(ycnt, ysum, v, radix)
    bits = OGB_TREE_SPLIT.bit_length() - 1
    done = 0
    while done < iters:
        r = min(bits, iters - done)
        k = 1 << r
        t = lo + (hi - lo) * (jnp.arange(1, k, dtype=jnp.float32) / k)
        ok = _ogb_tree_mass(rows, total, wv, t, v, radix) >= cap
        # points[j] is the last point of the leading run of feasible ones
        # (lo itself when t[0] fails); hi closes the list as infeasible
        j = jnp.argmin(jnp.concatenate([ok, jnp.zeros(1, bool)]))
        points = jnp.concatenate([lo[None], t, hi[None]])
        lo, hi = points[j], points[j + 1]
        done += r
    return lo


@functools.lru_cache(maxsize=None)
def make_ogb_tree_chunk(catalog_size: int, v: int, radix: int, sample: str,
                        iters: int = OGB_TREE_ITERS):
    """Per-chunk lazy OGB step ``(carry, ids) -> (carry, (reward, hits,
    dtau, occ))``.

    Exactness notes (vs the dense chained projection):

    * the gradient step, hit accounting and reward are exact (B gathers of
      ``clip(y - rho, 0, 1)``);
    * the threshold solve uses the bucket mean-clip mass — exact except for
      the <= 2 buckets straddling ``rho`` and ``rho + 1``, so rho carries
      an O(bucket width) quantization.  It resolves ``iters`` halvings
      of the warm bracket ``[rho, rho + max(eta*B, 4w)]`` by the K-ary
      search of :func:`_ogb_tree_solve`: in exact arithmetic the result
      of ``iters`` bisection steps; in float32 its probe points are
      rounded differently, so rho may differ from bisection's by about
      the final bracket width plus an ulp;
    * a touched item is first re-anchored to the clipped f of the dense
      step (``y <- clip(y, rho, 1 + rho)``): one below ``rho`` holds f = 0,
      not a debt.  The upper clip is applied to an item only when it is
      touched, so an item far above the cap decays a little later than in
      the dense replay (bounded by its last chunk's eta mass).  The
      differential test bounds the combined drift.
    """
    poisson = sample == "poisson"

    def chunk(carry, ids):
        b = ids.shape[0]
        y, rho, eta, cap = carry.y, carry.rho, carry.eta, carry.cap
        p, wv, scratch = carry.p, carry.w, carry.scratch
        ycnt, ysum, dcnt = carry.ycnt, carry.ysum, carry.dcnt
        lanes = jnp.arange(b, dtype=jnp.int32)

        # each phase runs under a named scope (ogb_tree/<phase>), so the
        # ops of a device trace name the phase they belong to
        # --- metrics at the pre-update state (OCO order), O(B) gathers ---
        with jax.named_scope("ogb_tree/metrics"):
            fi = jnp.clip(y[ids] - rho, 0.0, 1.0)
            reward = jnp.sum(fi)
            if poisson:
                hits = jnp.sum((fi >= p[ids]).astype(jnp.int32))
                # occupancy #{y - p >= rho} from the d-tree: suffix count
                # above rho's bucket (quantized at the boundary bucket)
                dtot = pt.tree_total(dcnt, v, radix)
                occ = dtot - pt.tree_prefix(
                    dcnt, v, radix, _ogb_bucket(rho, wv, v)[None]
                )[0]
            else:
                hits = jnp.zeros((), jnp.int32)
                occ = cap

        # --- first-occurrence mask (dedup without sorting) ---
        with jax.named_scope("ogb_tree/dedup"):
            a = scratch.at[ids].min(lanes)
            first = a[ids] == lanes
            scratch = a.at[ids].set(_I32_MAX)  # restore

        # --- gradient step: clip touched items, add eta per request ---
        with jax.named_scope("ogb_tree/gradient"):
            yold = y[ids]
            y = y.at[ids].min(1.0 + rho).at[ids].max(rho)
            y = y.at[ids].add(eta)
            ynew = y[ids]

        # --- move touched items between buckets (one per distinct item) ---
        with jax.named_scope("ogb_tree/update"):
            bo = jnp.where(first, _ogb_bucket(yold, wv, v), -1)
            bn = jnp.where(first, _ogb_bucket(ynew, wv, v), -1)
            didx = jnp.concatenate([bo, bn])
            ones = jnp.ones(b, jnp.float32)
            ycnt = pt.tree_update(ycnt, v, radix, didx,
                                  jnp.concatenate([-ones, ones]))
            ysum = pt.tree_update(
                ysum, v, radix, didx,
                jnp.concatenate([
                    jnp.where(first, -yold, 0.0),
                    jnp.where(first, ynew, 0.0),
                ]),
            )
            if poisson:
                do = jnp.where(first, _ogb_bucket(yold - p[ids], wv, v), -1)
                dn = jnp.where(first, _ogb_bucket(ynew - p[ids], wv, v), -1)
                dcnt = pt.tree_update(dcnt, v, radix,
                                      jnp.concatenate([do, dn]),
                                      jnp.concatenate([-ones, ones]))

        # --- scalar threshold solve: K-ary search on the warm bracket ---
        with jax.named_scope("ogb_tree/solve"):
            total = pt.tree_total(ycnt, v, radix)
            # rho* - rho <= eta*B (chained-projection bound); the 4w floor
            # keeps the bracket wider than the mass quantization when
            # eta*B < w
            hi0 = rho + jnp.maximum(eta * jnp.float32(b), 4.0 * wv)
            rho_new = _ogb_tree_solve(ycnt, ysum, wv, total, cap, rho, hi0,
                                      v, radix, iters)

        # --- re-anchor when the next chunk could outgrow the value grid ---
        gridtop = wv * jnp.float32(v) - 1.0

        def reanchor(args):
            # scoped inside the branch: the scope's time is zero on every
            # chunk that does not re-anchor
            with jax.named_scope("ogb_tree/reanchor"):
                y, rho_new, ycnt, ysum, dcnt = args
                y = jnp.clip(y - rho_new, 0.0, 1.0)
                by = _ogb_bucket(y, wv, v)
                onesn = jnp.ones_like(y)
                cl = jnp.zeros(v, jnp.float32).at[by].add(onesn)
                sl = jnp.zeros(v, jnp.float32).at[by].add(y)
                ycnt = pt.tree_build(cl, radix)
                ysum = pt.tree_build(sl, radix)
                if poisson:
                    dl = jnp.zeros(v, jnp.float32).at[
                        _ogb_bucket(y - p, wv, v)
                    ].add(onesn)
                    dcnt = pt.tree_build(dl, radix)
                return y, jnp.float32(0.0), ycnt, ysum, dcnt

        y, rho_out, ycnt, ysum, dcnt = jax.lax.cond(
            1.0 + rho_new + eta * jnp.float32(b) >= gridtop - wv,
            reanchor,
            lambda args: args,
            (y, rho_new, ycnt, ysum, dcnt),
        )
        out = carry._replace(y=y, rho=rho_out, scratch=scratch,
                             ycnt=ycnt, ysum=ysum, dcnt=dcnt)
        return out, (reward, hits, rho_new - rho, occ)

    return chunk


# ---------------------------------------------------------------------------
# sized OGB: per-size-class bucket trees, O(K * B log V) per chunk
# ---------------------------------------------------------------------------
#: default number of size (slab) classes the sized tree flavor quantizes to
SIZED_OGB_CLASSES = 16


class SizedOGBTreeCarry(NamedTuple):
    """Lazy *weighted* OGB state over K size classes (paper §8 setting).

    The knapsack-relaxed projection onto {f : sum_i s_i f_i = C} is
    f_i = clip(y_i - s_k * rho, 0, 1) for item i in size class k — the
    uniform-subtraction trick generalizes per class, so the unit-size
    bucket-histogram solve becomes K stacked histograms, one per slab
    class, each with a class-scaled bucket width w_k = s_k * wb (uniform
    rho resolution across classes).  A chunk touches O(K * B log V) tree
    nodes; the catalog is only visited on re-anchor.

    Sizes/costs are pre-normalized by the mean slab size (``sref``), so
    uniform sizes reduce to the unit ``ogb_tree`` dynamics at the same
    eta; byte outputs are scaled back by ``sref``.
    """

    y: jax.Array  # (N,) float32 accumulated values
    rho: jax.Array  # () float32 cumulative base multiplier
    eta: jax.Array  # () float32
    cap: jax.Array  # () float32 capacity in normalized bytes
    cls: jax.Array  # (N,) int32 item -> size class
    s: jax.Array  # (K,) float32 normalized class sizes
    wts: jax.Array  # (N,) float32 normalized gradient weights (costs)
    sref: jax.Array  # () float32 bytes per normalized size unit
    wmax: jax.Array  # () float32 max gradient weight (re-anchor headroom)
    p: jax.Array  # (N,) float32 permanent random numbers, or (0,)
    wb: jax.Array  # () float32 base bucket width (class k: s_k * wb)
    scratch: jax.Array  # (N,) int32 first-occurrence dedup scratch
    ycnt: jax.Array  # (K, TOT) float32 per-class bucket-count trees
    ysum: jax.Array  # (K, TOT) float32 per-class bucket-sum trees
    dcnt: jax.Array  # (K, TOT) float32 trees over y - p, or (0, TOT)


def _stacked_tree_update(trees, v: int, radix: int, rows, idx, delta):
    """Batched point update on stacked per-class trees ``(K, TOT)``:
    add ``delta[q]`` along the ancestor path of leaf ``idx[q]`` in the
    class-``rows[q]`` tree; ``idx < 0`` entries are skipped."""
    kk, tot = trees.shape
    offs = pt.tree_offsets(v, radix)
    sh = radix.bit_length() - 1
    ok = idx >= 0
    node = jnp.where(ok, idx, 0)
    row = jnp.where(ok, rows, 0) * tot
    nodes, deltas = [], []
    zero = jnp.zeros((), delta.dtype)
    for off in offs:
        nodes.append(row + off + node)
        deltas.append(jnp.where(ok, delta, zero))
        node = node >> sh
    flat = trees.reshape(-1).at[jnp.concatenate(nodes)].add(
        jnp.concatenate(deltas)
    )
    return flat.reshape(kk, tot)


def init_sized_ogb_tree_carry(
    catalog_size: int,
    capacity: float,
    *,
    sizes: np.ndarray,
    costs: Optional[np.ndarray] = None,
    eta: float,
    seed: int = 0,
    sample: str = "poisson",
    classes: int = SIZED_OGB_CLASSES,
    buckets: int = OGB_TREE_BUCKETS,
    radix: int = OGB_TREE_RADIX,
    batch_hint: int = 4096,
) -> SizedOGBTreeCarry:
    """Initial carry at the uniform feasible state f = C / sum_i s_i.

    ``sizes`` (bytes) are quantized to at most ``classes`` slab sizes
    (exact when there are that few distinct sizes — see
    :func:`repro.core.ogb_sized.size_classes`); ``costs`` default to the
    (quantized) sizes, i.e. byte-weighted rewards w_{t,i} = s_i."""
    from repro.cachesim.engines import sampling_keys
    from repro.core.ogb_sized import size_classes

    n, v = int(catalog_size), int(buckets)
    s_cls, cls = size_classes(sizes, classes)  # validates sizes > 0
    if not np.isfinite(capacity) or capacity <= 0:
        raise ValueError(f"capacity must be finite and > 0: {capacity!r}")
    sref = float(np.mean(s_cls[cls]))
    s_n = (s_cls / sref).astype(np.float64)  # normalized class sizes
    sq = s_n[cls]  # (N,) normalized per-item size
    if costs is None:
        w = sq.copy()
    else:
        w = np.asarray(costs, np.float64) / sref
        if w.shape != (n,):
            raise ValueError(f"costs must be a ({n},) array")
        if not (np.all(np.isfinite(w)) and w.min() > 0.0):
            raise ValueError("costs must be finite and > 0")
    cap_n = float(capacity) / sref
    total_s = float(np.sum(sq))
    if cap_n >= total_s:
        raise ValueError(
            f"capacity {capacity} holds the whole catalog "
            f"({sref * total_s:.0f} bytes); caching is trivial"
        )
    f0 = cap_n / total_s  # uniform feasible: sum_i s_i * f0 = cap_n
    wmax = float(np.max(w))
    smin = float(np.min(s_n))
    # base grid width: class-k grids span s_k * wb * v, sized so the
    # smallest class clears ~2*GAIN chunks of worst-case rho growth
    wb = (2.0 / smin + 2.0 * OGB_TREE_GAIN
          * max(1.0, float(eta) * batch_hint * wmax)) / v
    p, _ = sampling_keys(seed, n, sample)
    kk = len(s_n)
    w_k = s_n * wb  # per-class bucket widths
    by = np.clip(
        np.floor((f0 + 1.0) / w_k[cls]), 0, v - 1
    ).astype(np.int64)
    flatb = cls.astype(np.int64) * v + by
    cnt_leaf = np.bincount(flatb, minlength=kk * v).reshape(kk, v)
    sum_leaf = (cnt_leaf * f0).astype(np.float32)
    build = jax.vmap(lambda leaf: pt.tree_build(leaf, radix))
    ycnt = build(jnp.asarray(cnt_leaf, jnp.float32))
    ysum = build(jnp.asarray(sum_leaf))
    if sample == "poisson":
        d0 = f0 - np.asarray(p, np.float64)
        db = np.clip(np.floor((d0 + 1.0) / w_k[cls]), 0, v - 1).astype(
            np.int64
        )
        dl = np.bincount(
            cls.astype(np.int64) * v + db, minlength=kk * v
        ).reshape(kk, v)
        dcnt = build(jnp.asarray(dl, jnp.float32))
    else:
        dcnt = jnp.zeros((0, pt.tree_storage(v, radix)), jnp.float32)
    return SizedOGBTreeCarry(
        y=jnp.full(n, f0, jnp.float32),
        rho=jnp.zeros((), jnp.float32),
        eta=jnp.float32(eta),
        cap=jnp.float32(cap_n),
        cls=jnp.asarray(cls, jnp.int32),
        s=jnp.asarray(s_n, jnp.float32),
        wts=jnp.asarray(w, jnp.float32),
        sref=jnp.float32(sref),
        wmax=jnp.float32(wmax),
        p=p,
        wb=jnp.float32(wb),
        scratch=jnp.full(n, _I32_MAX, jnp.int32),
        ycnt=ycnt,
        ysum=ysum,
        dcnt=dcnt,
    )


@functools.lru_cache(maxsize=None)
def make_sized_ogb_tree_chunk(catalog_size: int, kk: int, v: int, radix: int,
                              sample: str, iters: int = OGB_TREE_ITERS):
    """Per-chunk sized lazy OGB step ``(carry, ids) -> (carry, (reward,
    hits, byte_hits, drho, occ_bytes))``.

    The scalar solve finds the base multiplier rho with

        sum_k s_k * m_k(s_k * rho) = C,   m_k = class-k mean-clip bucket mass

    by warm-bracketed safeguarded Newton: each iteration reads 2 prefix
    sums per class (O(K log V)), the slope is sum_k s_k^2 * (interior
    count)_k, and the bisection bracket [rho, wb * v] guards the Newton
    proposals.  Same quantization caveats as the unit ``ogb_tree``, with
    the bucket width scaled per class so rho resolution is uniform."""
    poisson = sample == "poisson"

    def class_mass(ycnt_k, ysum_k, wv_k, t_k):
        """(mass, interior count) of one class at class-threshold t_k."""
        k0 = _ogb_bucket(t_k, wv_k, v)
        k1 = _ogb_bucket(t_k + 1.0, wv_k, v)
        total = pt.tree_total(ycnt_k, v, radix)
        qc = pt.tree_prefix(ycnt_k, v, radix, jnp.stack([k0, k1]))
        qs = pt.tree_prefix(ysum_k, v, radix, jnp.stack([k0, k1]))
        cb = jnp.stack([ycnt_k[k0], ycnt_k[k1]])
        sb = jnp.stack([ysum_k[k0], ysum_k[k1]])
        above = total - qc[1]
        mid_c = qc[1] - cb[1] - qc[0]
        mid_s = qs[1] - sb[1] - qs[0]
        mid = mid_s - t_k * mid_c
        mean = jnp.where(cb > 0, sb / jnp.maximum(cb, 1.0), 0.0)
        bclip = jnp.clip(mean - t_k, 0.0, 1.0)
        bnd = cb * bclip
        bint = jnp.where((bclip > 0.0) & (bclip < 1.0), cb, 0.0)
        mass = above + mid + bnd[0] + jnp.where(k1 > k0, bnd[1], 0.0)
        interior = mid_c + bint[0] + jnp.where(k1 > k0, bint[1], 0.0)
        return mass, interior

    vclass_mass = jax.vmap(class_mass, in_axes=(0, 0, 0, 0))

    def chunk(carry, ids):
        b = ids.shape[0]
        y, rho, eta, cap = carry.y, carry.rho, carry.eta, carry.cap
        cls, s, wts, sref = carry.cls, carry.s, carry.wts, carry.sref
        p, wb, scratch = carry.p, carry.wb, carry.scratch
        ycnt, ysum, dcnt = carry.ycnt, carry.ysum, carry.dcnt
        lanes = jnp.arange(b, dtype=jnp.int32)
        w_k = s * wb  # (K,) per-class bucket widths

        cj = cls[ids]
        sj = s[cj]
        wj = wts[ids]

        # --- metrics at the pre-update state (OCO order) ---
        fi = jnp.clip(y[ids] - sj * rho, 0.0, 1.0)
        reward = jnp.sum(wj * fi)
        if poisson:
            hflag = fi >= p[ids]
            hits = jnp.sum(hflag.astype(jnp.int32))
            byte_hits = jnp.sum(jnp.where(hflag, sj, 0.0)) * sref
            # byte occupancy: per-class suffix counts of y - p above the
            # class threshold s_k * rho, weighted by class bytes
            dtots = jax.vmap(lambda tr: pt.tree_total(tr, v, radix))(dcnt)
            dpre = jax.vmap(
                lambda tr, q: pt.tree_prefix(tr, v, radix, q[None])[0]
            )(dcnt, _ogb_bucket(s * rho, w_k, v))
            occ = jnp.sum(s * (dtots - dpre)) * sref
        else:
            hits = jnp.zeros((), jnp.int32)
            byte_hits = jnp.zeros((), jnp.float32)
            occ = cap * sref

        # --- first-occurrence mask (dedup without sorting) ---
        a = scratch.at[ids].min(lanes)
        first = a[ids] == lanes
        scratch = a.at[ids].set(_I32_MAX)

        # --- gradient step: clip touched, add eta * w_j per request ---
        yold = y[ids]
        y = y.at[ids].min(1.0 + sj * rho).at[ids].max(sj * rho)
        y = y.at[ids].add(eta * wj)
        ynew = y[ids]

        # --- move touched items between their class buckets ---
        wvj = w_k[cj]
        bo = jnp.where(first, _ogb_bucket(yold, wvj, v), -1)
        bn = jnp.where(first, _ogb_bucket(ynew, wvj, v), -1)
        rows2 = jnp.concatenate([cj, cj])
        didx = jnp.concatenate([bo, bn])
        ones = jnp.ones(b, jnp.float32)
        ycnt = _stacked_tree_update(ycnt, v, radix, rows2, didx,
                                    jnp.concatenate([-ones, ones]))
        ysum = _stacked_tree_update(
            ysum, v, radix, rows2, didx,
            jnp.concatenate([
                jnp.where(first, -yold, 0.0), jnp.where(first, ynew, 0.0)
            ]),
        )
        if poisson:
            do = jnp.where(first, _ogb_bucket(yold - p[ids], wvj, v), -1)
            dn = jnp.where(first, _ogb_bucket(ynew - p[ids], wvj, v), -1)
            dcnt = _stacked_tree_update(dcnt, v, radix, rows2,
                                        jnp.concatenate([do, dn]),
                                        jnp.concatenate([-ones, ones]))

        # --- threshold solve: warm-bracketed safeguarded Newton on rho ---
        gridtop = wb * jnp.float32(v)

        def sweep_iter(_, state):
            lo, hi, t = state
            masses, interior = vclass_mass(ycnt, ysum, w_k, s * t)
            mass = jnp.sum(s * masses)
            slope = jnp.sum(s * s * interior)
            too_much = mass >= cap
            lo = jnp.where(too_much, t, lo)
            hi = jnp.where(too_much, hi, t)
            t_newton = t + (mass - cap) / jnp.maximum(slope, 1e-12)
            t_mid = 0.5 * (lo + hi)
            ok = jnp.logical_and(
                slope > 0.0,
                jnp.logical_and(t_newton > lo, t_newton < hi),
            )
            return lo, hi, jnp.where(ok, t_newton, t_mid)

        rho_new, _, _ = jax.lax.fori_loop(
            0, iters, sweep_iter, (rho, gridtop, rho)
        )

        # --- re-anchor when any class could outgrow its value grid ---
        def reanchor(args):
            y, rho_new, ycnt, ysum, dcnt = args
            scl = s[cls]
            y = jnp.clip(y - scl * rho_new, 0.0, 1.0)
            wcl = w_k[cls]
            by = _ogb_bucket(y, wcl, v)
            onesn = jnp.ones_like(y)
            cl = jnp.zeros((kk, v), jnp.float32).at[cls, by].add(onesn)
            sl = jnp.zeros((kk, v), jnp.float32).at[cls, by].add(y)
            build = jax.vmap(lambda leaf: pt.tree_build(leaf, radix))
            ycnt = build(cl)
            ysum = build(sl)
            if poisson:
                dl = jnp.zeros((kk, v), jnp.float32).at[
                    cls, _ogb_bucket(y - p, wcl, v)
                ].add(onesn)
                dcnt = build(dl)
            return y, jnp.float32(0.0), ycnt, ysum, dcnt

        trig = jnp.any(
            1.0 + s * rho_new + eta * carry.wmax * jnp.float32(b)
            >= w_k * jnp.float32(v) - 1.0 - w_k
        )
        y, rho_out, ycnt, ysum, dcnt = jax.lax.cond(
            trig, reanchor, lambda args: args, (y, rho_new, ycnt, ysum, dcnt)
        )
        out = carry._replace(y=y, rho=rho_out, scratch=scratch,
                             ycnt=ycnt, ysum=ysum, dcnt=dcnt)
        return out, (reward, hits, byte_hits, rho_new - rho, occ)

    return chunk


# ---------------------------------------------------------------------------
# unified entry points (mirrors engines.init_engine_carry / _STEPS)
# ---------------------------------------------------------------------------
def init_tree_engine_carry(
    kind: str,
    catalog_size: int,
    capacity: int,
    *,
    n_slots: Optional[int] = None,
    seed: int = 0,
    zeta: Optional[float] = None,
    horizon: Optional[int] = None,
    ring: Optional[int] = None,
):
    if kind == "lru":
        return init_tree_lru_carry(catalog_size, capacity, n_slots, ring)
    if kind == "lfu":
        return init_tree_lfu_carry(catalog_size, capacity, n_slots)
    if kind == "ftpl":
        return init_tree_ftpl_carry(catalog_size, capacity, n_slots,
                                    seed=seed, zeta=zeta, horizon=horizon)
    if kind == "gds":
        return init_tree_gds_carry(catalog_size, capacity, n_slots)
    raise ValueError(
        f"unknown tree engine kind {kind!r} (have {TREE_ENGINE_KINDS})"
    )


def make_tree_chunk(kind: str, carry, return_flags: bool = False):
    """Chunk step ``(carry, ids) -> (carry, (hits, occupancy))`` matching
    the given carry's static geometry.  ``return_flags=True`` yields the
    (window,) per-request hit flags instead of the chunk sum, so sized
    callers can weight each hit by the requested item's bytes."""
    if kind == "lru":
        m = pt.leaves_for_storage(carry.tree.shape[0], RING_RADIX)
        inner = make_lru_tree_chunk(carry.last.shape[0] - 1, m,
                                    return_flags)

        def chunk(c, ids):
            c, (hits, occ) = inner(c, ids)
            return c, (hits, occ)

        return chunk
    if kind == "lfu":
        inner = make_lfu_tree_chunk(carry.imap.shape[0] - 1,
                                    carry.slots.shape[0], return_flags)
    elif kind == "ftpl":
        inner = make_ftpl_tree_chunk(carry.imap.shape[0] - 1,
                                     carry.slots.shape[0], return_flags)
    elif kind == "gds":
        inner = make_gds_tree_chunk(carry.imap.shape[0] - 1,
                                    carry.slots.shape[0], return_flags)
    else:
        raise ValueError(f"unknown tree engine kind {kind!r}")

    def chunk(c, ids):
        c, hits = inner(c, ids)
        occ = jnp.sum((c.slots >= 0).astype(jnp.int32))
        return c, (hits, occ)

    return chunk
