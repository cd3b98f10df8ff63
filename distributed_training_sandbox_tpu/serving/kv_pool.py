"""Sharded paged KV cache pool — the serving-side cache substrate.

One-shot decode (``models/generate.py``) gives every request a private
``(B, n_kv, S_max, hd)`` cache sized to its own prompt+new.  A server
cannot: requests arrive and finish continuously, so the cache must be a
FIXED pool whose blocks are reassigned between requests without
reallocating (or retracing) anything.  vLLM's paged layout, TPU-shaped:

  * per-layer POOLS of page blocks, ``(n_pages, page_size, n_kv, hd)``
    (a ``"latent"`` layer: ``(n_pages, page_size, W)``, see ``row_layout``)
    in ``cfg.dtype`` — or int8 codes + ``(n_pages, page_size, n_kv, 1)``
    f32 row scales via the same ``_quant_kv`` row quantizer the one-shot
    int8 cache uses;
  * a host-side PAGE TABLE per request slot: absolute position ``p`` of
    a request lives at ``(page_table[slot, p // page_size],
    p % page_size)``;
  * TWO PAGE CLASSES where some layers attend through a sliding window
    (kind ``"window"``): a ``"full"`` layer's pools are the pages
    above, granted for a request's whole length; a WINDOW layer needs its
    last ``sliding_window`` rows whatever the request's length, so the
    window layers' pools are a class of their own with its own
    ``n_pages``, its own :class:`PageAllocator` and a second table row a
    slot, of ``ring_pages`` entries used as a RING: position ``p`` lives
    at ``(table_w[slot, (p // page_size) % ring_pages], p % page_size)``
    (:func:`ring_pages`, :func:`ring_view`).  Page 0 of each class is its
    null page;
  * page 0 is RESERVED as the null page: writes for padded/inactive
    positions are diverted there (a scatter must always have a target —
    static shapes), and unassigned page-table entries point at it, so
    reads of dead slots land on masked garbage, never out of bounds;
  * under tensor parallelism the head axis (dim 2) is sharded over the
    mesh's ``tp`` axis — the same each-rank-caches-its-local-heads
    layout ``init_cache(tp=...)`` uses, so pool memory and per-step
    cache reads shrink by tp.

The device arrays live in a :class:`PoolBuffers` namedtuple that the
jitted decode/prefill steps DONATE and return — the pool object just
tracks the current buffers plus the free list.

What a layer keeps for a request follows from its KIND, and
:func:`layer_kinds` is the one list everything here is sized from (a block
module declares it: ``cfg.block_module.layer_kinds``; every layer of the
dense GQA block is ``"full"``).  It lists CACHES, which are not always one
a weight layer: a block whose layers run ``cfg.layer_passes`` = T times
with the same weights (``models/loop_dense.py``) keeps pass ``t``'s rows
apart from every other pass's, T caches a weight layer, ``T x L`` arrays in
:class:`PoolBuffers`.  The page table and the allocators do not change with
it: one grant of pages serves every cache, as it serves every layer.

  * ``"full"``: K and V rows of ``(n_kv, hd)`` a token, in whole-context
    pages;
  * ``"window"``: the same rows in a ring of the window page class;
  * ``"latent"``: ONE row ``[c_kv | k_rope]`` a token
    (``block_module.row_width``) and no V pool;
  * ``"linear"``: no pages (:func:`paged_layers` leaves it out) but, per
    REQUEST and whatever its length, one float32 state matrix and a conv
    tail.  Those live beside the pages in the same :class:`PoolBuffers` as
    fixed-size STATE SLOTS indexed by the batch slot the scheduler grants,
    shaped as ``cfg.linear_mixer`` says: ``state`` ``(n_slots,) +
    slot_shape`` float32, lane-dense (the gated delta rule's ``(key dim,
    heads * value dim)``: 96 x 5,760 at the published widths, whole (8, 128)
    tiles, so that a slot occupies its 2,211,840 bytes and a kernel's block
    copy moves no padding, where ``(heads, 96, 192)`` held every row of 192
    in 256 lanes, 2,949,120 bytes; Mamba-2's ``(state dim, heads * head
    dim)``: 128 x 8,192, 4,194,304 bytes of whole tiles as the state is laid
    out), and ``conv`` ``(n_slots, K - 1, channels)``.  A slot's state never
    survives its request: the first prefill chunk of the next one starts
    from zeros whatever the slot held (``engine._prefill_core``).

What ONE token caches in one paged layer is the pool's ROW, which
:func:`row_layout` states; the pool alone knows how a row is padded to the
chip's tiles (``LATENT_ROW_ALIGN``, :func:`padded_kv_heads`,
:func:`slab_pool`).  Pages, the allocator and the prefix trie are
page-granular and do not look inside a row.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

#: a latent row is padded with zero columns to whole 128-lane tiles: a TPU
#: holds a bf16 array's minor dimension in tiles of 128 anyway (576 takes
#: 640 columns), and the decode kernel copies a page as whole tiles
LATENT_ROW_ALIGN = 128


#: kinds whose layers hold something in a request's batch SLOT
SLOT_KINDS = ("linear", "conv_full")


def slot_kinds(cfg) -> list[str]:
    """The kinds of the layers that hold a slot, in layer order (empty for
    a block whose every layer only pages)."""
    return [kind for kind in layer_kinds(cfg) if kind in SLOT_KINDS]


def layer_kinds(cfg) -> tuple[str, ...]:
    """One entry a CACHE, of ``"full"``, ``"window"``, ``"latent"``,
    ``"linear"``, ``"conv_full"`` (module docstring), in the order the
    serving loop walks them: the block module's declaration, one entry a
    weight layer, once a pass (``cfg.layer_passes``: cache ``t . L + l`` is
    pass ``t`` of weight layer ``l``; one pass, so one cache a layer, for
    every block but the looped one); every layer of the dense block is
    ``"full"``."""
    blk = cfg.block_module
    if blk is None:
        return ("full",) * cfg.num_hidden_layers
    return blk.layer_kinds(cfg) * cfg.layer_passes


def padded_kv_heads(n_kv: int, dtype) -> int:
    """KV heads a row of the full-attention pools holds beside state
    slots: ``n_kv``, rounded up to whole sublane tiles of the dtype
    (8 rows of 32 bits: 16 heads of bfloat16) unless it divides one.  The
    paged kernels read a page as ONE ``(page_size * heads, hd)`` slab; a TPU
    holds the pool's ``(heads, hd)`` minor dims in such tiles, so with 30
    heads of bfloat16 (32 rows a token in memory) that slab is no view of
    the pool and XLA copies the whole pool, several times a layer and step,
    to make one (compiled for a v5e: four 755 MB copies a pool).  Two zero
    heads make the slab a bitcast, as the latent row's zero columns do."""
    tile = 32 // jnp.dtype(dtype).itemsize
    if tile % n_kv == 0:
        return n_kv
    return n_kv + -n_kv % tile


def row_layout(cfg, tp: int = 1) -> tuple[tuple[int, ...], bool]:
    """``(row shape, has_v)`` of one token in one PAGED layer's pool(s):
    the trailing dims of every pool array after ``(n_pages, page_size)``,
    and whether a V pool of the same shape stands beside the K pool."""
    kinds = layer_kinds(cfg)
    if "latent" in kinds:
        w = cfg.block_module.row_width(cfg)
        return (w + -w % LATENT_ROW_ALIGN,), False
    if slot_kinds(cfg):     # no int8 rows, no tp mesh: see ``slab_pool``
        return (padded_kv_heads(cfg.num_key_value_heads, cfg.dtype),
                cfg.resolved_head_dim), True
    return (cfg.num_key_value_heads // tp, cfg.resolved_head_dim), True


def slab_pool(cfg) -> bool:
    """Whether a paged layer's pools are STORED as the slab the paged
    kernels read, ``(n_pages, page_size * heads, hd)``, and not as
    ``(n_pages, page_size, heads, hd)``: where a head is wider than one
    128-lane tile and the row's heads do not fill whole sublane tiles.  A
    TPU holds the 4-D array's ``(heads, hd)`` minor dims in tiles of
    ``heads`` rows by 128 lanes, one lane tile after the other, so with 2
    heads of 256 a page is no ``(32, 256)`` slab of it and XLA copies the
    whole pool to make one, for every layer in every step (compiled for a
    v5e: twelve 268 MB copies a decode step).  At a head dim of 128 the
    4-D array's bytes ARE the slab's, whatever the head count, and the
    pool stays 4-D.  Only beside slots (``SLOT_KINDS``), where the engine
    refuses what still indexes a pool by ``(page, offset, head)``: int8 rows
    with their scales, the hand-over between pools, a tp mesh over the head
    axis.  A slab's rows hold the model's heads and no zero head (heads that
    were padded fill whole sublane tiles), which the engine relies on."""
    if not slot_kinds(cfg):
        return False
    (heads, hd), _ = row_layout(cfg)
    return hd > 128 and heads % (32 // jnp.dtype(cfg.dtype).itemsize) != 0


def pool_shape(cfg, n_pages: int, page_size: int) -> tuple[int, ...]:
    """The shape of one paged layer's pool array as stored."""
    row, _ = row_layout(cfg)
    if slab_pool(cfg):
        return (n_pages, page_size * row[0], row[1])
    return (n_pages, page_size) + row


def ring_pages(cfg, page_size: int, prefill_chunk: int) -> int:
    """Entries of a slot's WINDOW-class table row, the ring: ``(sliding_window
    + prefill_chunk) / page_size``.  That many rows is the least ring in
    which the last write of a prefill chunk cannot land on a row the chunk's
    first query still sees (``tests/test_swa_moe.py`` shows a ring one page
    shorter fail); a decode step needs less."""
    if cfg.sliding_window % page_size or prefill_chunk % page_size:
        raise ValueError(
            f"sliding_window={cfg.sliding_window} and prefill_chunk="
            f"{prefill_chunk} must be whole pages of {page_size}: the "
            f"window layers' ring is counted in pages")
    return (cfg.sliding_window + prefill_chunk) // page_size


def ring_view(ring, apos, window: int, page: int):
    """The reading side of a window layer, once a launch: the slots' ring
    rows ``ring`` (B, R) rotated into ORDERED view tables for the rows at
    the consecutive absolute positions ``apos`` (B, S).  Entry ``j`` of a
    slot's view is the page that holds positions ``(p0 + j) * page``
    onwards, ``p0`` the page of the first position its first row sees, so
    the paged kernels and the gather path read it as they read a full
    layer's table.  Returns ``(view (B, R), apos in view coordinates (B,
    S), lo (B, S))``: ``lo`` is the first view position a row sees (the
    rows of the view's first page before it are not the row's), and a row
    at view position ``a`` sees ``lo <= s <= a``."""
    p0 = jnp.maximum(apos[:, :1] - window + 1, 0) // page       # (B, 1)
    R = ring.shape[1]
    view = jnp.take_along_axis(
        ring, (p0 + jnp.arange(R, dtype=jnp.int32)) % R, axis=1)
    base = p0 * page
    return view, apos - base, jnp.maximum(apos - window + 1, 0) - base


def paged_layers(cfg) -> int:
    """Caches in which a token holds a row in pages: what every sizing of
    the pool multiplies :func:`token_row_bytes` by.  One a layer a pass (of
    either page class), but for the linear layers, which hold a state slot
    instead."""
    return sum(kind != "linear" for kind in layer_kinds(cfg))


def tail_shape(cfg, kind: str) -> tuple[int, ...]:
    """One slot's conv tail in one layer of a slot ``kind``, as the linear
    mixer or, for ``"conv_full"``, the block module states it."""
    owner = cfg.linear_mixer if kind == "linear" else cfg.block_module
    return owner.tail_shape(cfg)


def slot_state_bytes(cfg) -> int:
    """Bytes ONE batch slot holds in slots over all layers (0 for a block
    that holds nothing a slot): a linear layer's state and tail, a
    ``"conv_full"`` layer's tail."""
    item = jnp.dtype(cfg.dtype).itemsize
    return sum(cfg.linear_mixer.slot_state_bytes(cfg) if kind == "linear"
               else math.prod(tail_shape(cfg, kind)) * item
               for kind in slot_kinds(cfg))


def token_row_bytes(cfg, *, kv_quant: bool = False, tp: int = 1) -> int:
    """Bytes one token occupies in ONE layer's pool(s), row scales of an
    int8 pool included: what every sizing of the pool multiplies."""
    shape, has_v = row_layout(cfg, tp)
    elems = math.prod(shape) * (2 if has_v else 1)
    if kv_quant:
        return elems + (elems // shape[-1]) * 4
    return elems * jnp.dtype(cfg.dtype).itemsize


class PoolBuffers(NamedTuple):
    """The device half of the pool: per-cache page-block arrays (tuples
    of L arrays, one per PAGED layer and pass, mirroring ``KVCache``'s
    per-layer-buffer decision — a stacked (L, ...) layout would pay a
    dynamic-slice copy per layer per step), each ``(n_pages, page_size) +
    row shape`` (:func:`row_layout`); with two page classes a WINDOW
    layer's arrays have the window class's ``n_pages_window`` pages and a
    full layer's the full class's ``n_pages``, in layer order.  ``v`` is
    None where the layers are ``"latent"``, whose one row a token lives in
    ``k``.  ``k_scale``/``v_scale`` are the
    f32 row scales of the int8 pool, None for the ``cfg.dtype`` pool.
    ``state`` are the state slots of the ``"linear"`` layers, one array a
    linear layer, ``conv`` the conv tails of the layers that hold one
    (``"linear"`` and ``"conv_full"``), one array a such layer in layer
    order; None without."""
    k: tuple            # L × (n_pages, page_size, n_kv, hd) | (.., .., W)
    #                     | (n_pages, page_size * n_kv, hd): slab_pool
    #                     | a window layer: (n_pages_window, page_size, ..)
    v: tuple | None
    k_scale: tuple | None   # L × (n_pages, page_size, n_kv, 1) f32
    v_scale: tuple | None
    state: tuple | None = None  # (n_slots, dk, heads * dv) f32 a linear layer
    conv: tuple | None = None   # (n_slots, K - 1, channels) a linear layer
    #                             | (n_slots, 2 C + hd) a conv_full layer


class PageAllocator:
    """Host-side free list over pages ``1..n_pages-1`` (page 0 is the
    reserved null page).  LIFO reuse keeps recently-touched pages warm;
    allocation is all-or-nothing so a request can never deadlock holding
    a partial page set."""

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError(f"need >= 2 pages (1 null + 1 usable), "
                             f"got {n_pages}")
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, 0, -1))

    def alloc(self, n: int) -> list[int] | None:
        """``n`` pages or None — never a partial grant."""
        if n <= 0:
            raise ValueError(f"alloc({n})")
        if len(self._free) < n:
            return None
        got = self._free[-n:]
        del self._free[-n:]
        return got

    def free(self, pages: list[int]) -> None:
        for p in pages:
            if not 0 < p < self.n_pages:
                raise ValueError(f"freeing invalid page {p}")
        self._free.extend(pages)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    @property
    def utilization(self) -> float:
        usable = self.n_pages - 1
        return self.pages_in_use / usable if usable else 0.0


class _TrieNode:
    """One cached full page of prompt KV: the page-size token chunk that
    keys it under its parent, the pool page holding those positions'
    K/V rows, and the in-flight refcount."""
    __slots__ = ("key", "page", "parent", "children", "refs", "last_used")

    def __init__(self, key: tuple, page: int, parent):
        self.key = key
        self.page = page
        self.parent = parent          # _TrieNode | None (root child)
        self.children: dict = {}
        self.refs = 0
        self.last_used = 0

    @property
    def depth(self) -> int:
        d, n = 1, self.parent
        while n is not None:
            d, n = d + 1, n.parent
        return d


class RadixPrefixCache:
    """Token-trie over committed KV pages — the prefix-reuse substrate.

    Nodes are PAGE-granular: each trie edge is an exact ``page_size``
    token chunk, so a node's path from the root spells out a full-page
    prompt prefix and its ``page`` holds exactly those positions' K/V
    rows.  Content identity is positional: K/V at absolute position
    ``p`` depends only on the token at ``p`` and ``p`` itself (per-row
    bitwise independence, the engine's parity invariant), so two
    requests sharing a page-aligned token prefix can alias the same
    pages and stay bitwise-identical to their private-cache runs.

    Ownership: pages referenced by the trie are OWNED by the trie —
    they are never on the allocator's free list and are returned to it
    only by :meth:`evict`.  Requests hold refcounts on the nodes they
    alias (``acquire``/``release``); eviction takes refcount-0 LEAF
    nodes in LRU order, so an in-flight request can never lose a page
    under it and interior nodes never orphan their children.

    Copy-on-write falls out of page granularity: the first divergent
    page has a different token chunk, so it simply isn't in the trie —
    admission allocates a fresh page for it and prefill recomputes from
    the matched boundary.  Aliased pages are never scatter targets
    (prefill starts at the matched page boundary; decode writes at
    positions past the prompt), which the CoW test pins byte-for-byte.

    The last prompt page is never cached even when full: prefill must
    run at least the final prompt position to produce the first token's
    logits, so matchable pages are capped at ``(n_prompt - 1) //
    page_size``.
    """

    def __init__(self, allocator: PageAllocator, page_size: int):
        self.allocator = allocator
        self.page_size = int(page_size)
        self._root: dict = {}         # key chunk -> _TrieNode
        self._nodes: list[_TrieNode] = []
        self._clock = 0
        # counters the engine mirrors into telemetry / slo_report
        self.hit_pages = 0
        self.lookup_pages = 0
        self.evictions = 0
        self.inserted_pages = 0
        # refcount-0 node count, maintained O(1) at every transition.
        # Exact reclaimability: a holder always refs its node's whole
        # prefix path, so a refs-0 node's subtree is refs-0 throughout
        # and :meth:`evict` can drain all of it leaf-first.
        self._idle_pages = 0

    # ---- queries ------------------------------------------------------
    @property
    def cached_pages(self) -> int:
        """Pages owned by the trie (not in the allocator free list)."""
        return len(self._nodes)

    @property
    def reclaimable_pages(self) -> int:
        """Pages :meth:`evict` could free right now (refcount-0 nodes).
        ``can_accept`` credits these against a request's page grant —
        without the credit a saturated trie wedges dispatch forever
        while every replica sits idle (the fleet-sim-discovered
        livelock)."""
        return self._idle_pages

    @property
    def hit_rate(self) -> float:
        return self.hit_pages / self.lookup_pages if self.lookup_pages \
            else 0.0

    def _chunks(self, tokens) -> list[tuple]:
        p = self.page_size
        n_full = (len(tokens) - 1) // p
        return [tuple(int(t) for t in tokens[i * p:(i + 1) * p])
                for i in range(n_full)]

    def match(self, tokens) -> list[_TrieNode]:
        """Longest cached full-page prefix of ``tokens`` — the nodes
        whose pages an admitted request will alias.  Pure lookup: no
        refcounts taken, no counters (admission may retry the same
        head-of-line request many rounds; it calls :meth:`note_lookup`
        once on success)."""
        nodes: list[_TrieNode] = []
        kids = self._root
        for key in self._chunks(tokens):
            node = kids.get(key)
            if node is None:
                break
            nodes.append(node)
            kids = node.children
        return nodes

    def note_lookup(self, hit_pages: int, lookup_pages: int) -> None:
        self.hit_pages += hit_pages
        self.lookup_pages += lookup_pages

    # ---- refcounts ----------------------------------------------------
    def acquire(self, nodes: list[_TrieNode]) -> None:
        self._clock += 1
        for n in nodes:
            if n.refs == 0:
                self._idle_pages -= 1
            n.refs += 1
            n.last_used = self._clock

    def release(self, nodes: list[_TrieNode]) -> None:
        for n in nodes:
            if n.refs <= 0:
                raise ValueError("prefix-cache refcount underflow — "
                                 "double release")
            n.refs -= 1
            if n.refs == 0:
                self._idle_pages += 1

    # ---- growth -------------------------------------------------------
    def insert(self, tokens, pages: list[int],
               matched: list[_TrieNode]):
        """Donate a just-prefilled request's full-prompt pages into the
        trie.  ``pages`` is the request's page list (cached prefix
        first, then granted pages); ``matched`` the nodes it acquired at
        admission.  Returns ``(nodes, swaps)``: the full prefix-aligned
        node list (refs held by the caller) and a ``{page_index: page}``
        map for chunks a CONCURRENT twin already cached — the caller's
        duplicate page is freed and its page-table entry must be
        rewritten to the cached twin (contents are bitwise-identical,
        so the swap is invisible to decode)."""
        chunks = self._chunks(tokens)
        nodes = list(matched)
        swaps: dict[int, int] = {}
        self._clock += 1
        for i in range(len(matched), len(chunks)):
            kids = nodes[-1].children if nodes else self._root
            node = kids.get(chunks[i])
            if node is None:
                node = _TrieNode(chunks[i], pages[i],
                                 nodes[-1] if nodes else None)
                kids[chunks[i]] = node
                self._nodes.append(node)
                self.inserted_pages += 1
                self._idle_pages += 1   # born refs-0; claimed below
            elif node.page != pages[i]:
                # two requests with the same prefix prefilled
                # concurrently; adopt the cached twin, free ours
                swaps[i] = node.page
                self.allocator.free([pages[i]])
            if node.refs == 0:
                self._idle_pages -= 1
            node.refs += 1
            node.last_used = self._clock
            nodes.append(node)
        return nodes, swaps

    # ---- pressure -----------------------------------------------------
    def evict(self, n: int) -> int:
        """Free up to ``n`` pages by evicting refcount-0 LEAF nodes in
        LRU order (ties broken deepest-first so chains drain tail-in).
        Returns the number actually freed — the caller retries its
        allocation and sheds load if the trie couldn't give enough."""
        freed = 0
        while freed < n:
            victims = [nd for nd in self._nodes
                       if nd.refs == 0 and not nd.children]
            if not victims:
                break
            v = min(victims, key=lambda nd: (nd.last_used, -nd.depth))
            kids = v.parent.children if v.parent is not None \
                else self._root
            del kids[v.key]
            self._nodes.remove(v)
            self.allocator.free([v.page])
            self.evictions += 1
            self._idle_pages -= 1
            freed += 1
        return freed

    def stats(self) -> dict:
        return {"cached_pages": self.cached_pages,
                "hit_pages": self.hit_pages,
                "lookup_pages": self.lookup_pages,
                "hit_rate": round(self.hit_rate, 4),
                "evictions": self.evictions,
                "inserted_pages": self.inserted_pages}


class PagedKVPool:
    """Device pools + allocator + (optional) mesh sharding.

    ``mesh``/``tp_axis``: shard the head axis over ``tp_axis`` via a
    NamedSharding — the buffers stay one logical array addressed by the
    engine's ``shard_map`` step.  ``device``: commit the pool to one
    device (the disaggregated prefill/decode slices).  Neither: default
    placement."""

    def __init__(self, cfg, n_pages: int, page_size: int, *,
                 kv_quant: bool = False, mesh=None, tp_axis: str = "tp",
                 device=None, n_slots: int = 0, n_pages_window: int = 0):
        if mesh is not None and device is not None:
            raise ValueError("pass mesh or device, not both")
        kinds = layer_kinds(cfg)
        if "window" in kinds and (kv_quant or mesh is not None
                                  or n_pages_window < 2):
            raise ValueError(
                "the window + full attention block's pool has a second "
                "page class for its window layers: pass n_pages_window "
                ">= 2 (max_batch rings of kv_pool.ring_pages + the null "
                "page), and neither kv_quant nor a mesh")
        slotted = slot_kinds(cfg)
        if slotted and (kv_quant or mesh is not None or n_slots < 1):
            raise ValueError(
                "the pool of a block with linear layers, or with conv "
                "tails beside its pages, holds a float slot per batch "
                "slot: pass n_slots >= 1, and neither kv_quant nor a mesh")
        self.cfg = cfg
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.kv_quant = bool(kv_quant)
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.device = device
        L = paged_layers(cfg)
        row, has_v = row_layout(cfg)
        if kv_quant and not has_v:
            raise NotImplementedError(
                "an int8 pool of latent rows is not built (the row "
                "quantizer scales per KV head)")
        shape = pool_shape(cfg, self.n_pages, self.page_size)
        dt = jnp.int8 if kv_quant else cfg.dtype
        put = self._put
        # the window layers' page class: pools of their own size, and the
        # free list the scheduler grants their rings from
        self.n_pages_window = int(n_pages_window) if "window" in kinds \
            else 0
        window = pool_shape(cfg, self.n_pages_window, self.page_size)
        shapes = [window if kind == "window" else shape
                  for kind in kinds if kind != "linear"]
        k = tuple(put(jnp.zeros(sh, dt)) for sh in shapes)
        v = tuple(put(jnp.zeros(sh, dt)) for sh in shapes) \
            if has_v else None
        # scales init to ones like init_cache's — unwritten rows then
        # dequantize to exact zeros, matching the one-shot cache
        ks = vs = None
        if kv_quant:
            ks = tuple(put(jnp.ones(shape[:-1] + (1,), jnp.float32))
                       for _ in range(L))
            vs = tuple(put(jnp.ones(shape[:-1] + (1,), jnp.float32))
                       for _ in range(L))
        state = conv = None
        n_lin = kinds.count("linear")
        self.n_slots = int(n_slots) if slotted else 0
        if self.n_slots:
            if n_lin:
                slot = (self.n_slots,) + cfg.linear_mixer.slot_shape(cfg)
                state = tuple(put(jnp.zeros(slot, jnp.float32))
                              for _ in range(n_lin))
            conv = tuple(put(jnp.zeros(
                (self.n_slots,) + tail_shape(cfg, kind), cfg.dtype))
                for kind in slotted)
        self.bufs = PoolBuffers(k=k, v=v, k_scale=ks, v_scale=vs,
                                state=state, conv=conv)
        self.allocator = PageAllocator(self.n_pages)
        self.window_allocator = PageAllocator(self.n_pages_window) \
            if self.n_pages_window else None

    def _row_spec(self):
        """One pool array's PartitionSpec: the KV-head axis over tp under
        a mesh; a latent row has no head axis and stays whole."""
        from jax.sharding import PartitionSpec as P
        row, _ = row_layout(self.cfg)
        if len(row) == 1:
            return P(None, None, None)
        return P(None, None, self.tp_axis if self.mesh is not None else None,
                 None)

    def _put(self, x):
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            return jax.device_put(
                x, NamedSharding(self.mesh, self._row_spec()))
        if self.device is not None:
            return jax.device_put(x, self.device)
        return x

    @property
    def spec(self) -> PoolBuffers:
        """PartitionSpec pytree matching ``bufs`` — the in/out spec the
        engine hands ``shard_map`` (heads sharded over tp, everything
        else replicated)."""
        L = paged_layers(self.cfg)
        ps = self._row_spec()
        sc = (ps,) * L if self.kv_quant else None
        return PoolBuffers(k=(ps,) * L,
                           v=(ps,) * L if self.bufs.v is not None else None,
                           k_scale=sc, v_scale=sc)

    @property
    def row_bytes(self) -> int:
        """Bytes one token occupies in one paged layer of this pool."""
        return token_row_bytes(self.cfg, kv_quant=self.kv_quant)

    @property
    def token_bytes(self) -> int:
        """Bytes one token occupies over all the layers that have pages."""
        return self.row_bytes * paged_layers(self.cfg)

    @property
    def state_bytes(self) -> int:
        """Bytes of the state slots, all slots and layers (0 without)."""
        return self.n_slots * slot_state_bytes(self.cfg)

    @property
    def utilization(self) -> float:
        return self.allocator.utilization
