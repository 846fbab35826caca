"""Paged KV cache: geometry, the HBM slab, and the host page allocator.

Why pages instead of one [S, max_len] cache per slot: decode is
HBM-bound (batch 16 gives 2,374 tok/s vs 251 at batch 1 on v5e —
results/text-bench-v5e.jsonl), so cache capacity IS serving capacity.
A contiguous per-slot cache reserves max_len tokens of HBM for every
request up front; real streams vary wildly in length, so most of that
is dead. Fixed-size pages from a shared slab (the PagedAttention idea)
let a short stream hold two pages while a long one holds thirty, and a
finished stream's pages go back to the pool the same step.

Page 0 is RESERVED as the null page: inactive slots' scatter writes
land there (the jitted step always writes S rows — masking is data, not
shape), page-table tails point there, and its validity row stays zero
so gathers through it never contribute to attention. The allocator
simply never hands it out.

Allocation is host-side (a free list) because page tables are host
inputs to the jitted step — the device program only ever gathers
through tables it is given, so there is no device-side bookkeeping to
keep coherent.

Prefix caching (PR 8) turns the free list into a three-state page pool:

  FREE       on the free list, contents meaningless
  REFERENCED refcount >= 1 — one or more slots gather through it.
             A page full of prompt tokens can additionally be
             REGISTERED under its chain hash (see chain_hash), at
             which point later requests with the same prefix attach
             to it instead of re-prefilling (refcount goes up).
  CACHED     refcount == 0 but still registered: no slot needs it,
             yet its KV bytes are intact, so a future prefix hit can
             revive it for free. Cached pages sit in an LRU and are
             the allocator's SECOND source of pages — alloc() prefers
             the free list, then evicts the least-recently-used
             cached page, and only then reports exhaustion.

Sharing is what makes copy-on-write necessary: a slot may only scatter
into a page it exclusively owns (`writable()`), otherwise the engine
allocates a fresh page and the jitted step copies the shared page's
contents before the write (engine.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from kubeml_tpu.models.base import CacheSpec


def chain_hash(prefix_digest: bytes, tokens: Sequence[int]) -> bytes:
    """Rolling content hash for prefix caching: the key of page i is
    H(key of page i-1, tokens of page i), with b"" as the root. Keying
    on the whole chain (not just the page's own tokens) means two
    prompts share a page ONLY when everything before it matches too —
    positional embeddings make identical tokens at different offsets
    produce different KV, so a flat per-page hash would alias them."""
    h = hashlib.sha256(prefix_digest)
    h.update(np.asarray(tokens, np.int32).tobytes())
    return h.digest()


def routing_digest(prompt: Sequence[int], page: int) -> bytes:
    """The fleet router's prefix-affinity key (serve/fleet.py): the
    chain hash of the FIRST FULL prompt page — exactly the first digest
    the prefix cache registers, so two prompts route to the same replica
    precisely when they would share that replica's cached page. Prompts
    shorter than one page can never register a page; they hash whole,
    which still keeps identical short prompts together."""
    page = max(1, int(page))
    toks = prompt[:page] if len(prompt) >= page else prompt
    return chain_hash(b"", list(toks))


@dataclasses.dataclass(frozen=True)
class PageGeometry:
    """Static shape of the paged cache — any change here recompiles, so
    everything per-request must live in the arrays, not here."""

    slots: int            # S: concurrent streams the step serves
    page: int             # G: tokens per page
    pages: int            # P: physical pages in the slab, incl. null page 0
    pages_per_slot: int   # Pmax: page-table width = context cap / G

    def __post_init__(self):
        if self.slots < 1 or self.page < 1 or self.pages_per_slot < 1:
            raise ValueError(f"degenerate page geometry: {self}")
        if self.pages < 2:
            raise ValueError("need at least one usable page besides the "
                             "reserved null page 0")

    @property
    def context(self) -> int:
        """Max tokens (prompt + generated) one slot can hold."""
        return self.pages_per_slot * self.page

    @property
    def usable_pages(self) -> int:
        return self.pages - 1  # page 0 is the null page

    @classmethod
    def for_module(cls, slots: int, page: int, max_len: int,
                   pages: int = 0) -> "PageGeometry":
        """Geometry sized so a slot can reach the module's max_len; by
        default the slab holds every slot at full context (no stalls),
        a smaller explicit `pages` turns on real contention."""
        pps = -(-max_len // page)
        return cls(slots=slots, page=page,
                   pages=pages or slots * pps + 1, pages_per_slot=pps)


# the serving KV storage dtypes the engine/CLI accept (--serve-kv-dtype):
# "f32" is the full-precision leg — pages stay in the module's own KV
# dtype (float32 models store f32, bfloat16 models bf16), the behavior
# every PR-8..14 bit-identity test pins; "int8" quantizes pages with one
# symmetric f32 scale per (layer, page) sidecar row.
KV_DTYPES = ("f32", "int8")


class KVPageSlab:
    """The device-resident arrays of a model family's cache, built from
    its declaration (models/base.py CacheSpec): `planes` page arrays for
    every layer and, where the family's programs carry them, the
    per-page int8 scales and the shared validity plane. GPT declares
    two planes (K, V) of H*Dh lanes with both; DeepSeek-V2 one plane of
    latent rows with neither.

    planes[i]: [L, P, G, row_lanes] in the cache dtype — ONE layout,
    lane-dense, that every serve program reads and writes in place. A
    token is one row of lanes per plane at [layer, page, offset]; a
    page is the [G, row_lanes] tile under it. For GPT a row is H*Dh
    lanes, head h in lanes [h*Dh, (h+1)*Dh). Heads ride the lane
    dimension because the TPU tiles an array's two minor dimensions
    (8 x 128 words): a minor pair (H, Dh) = (20, 64) fits no tile, so
    the client, the scatters and the kernel each picked another padded
    layout for a 5-D slab and every program relaid the whole slab out
    at its edges (PERF.md, PR 26). (G, H*Dh) = (16, 1280) tiles exactly
    in bf16, row-major is everyone's layout, and nothing is padded. A
    family whose row is no whole number of lane tiles (DeepSeek-V2's
    576) declares the padded `row_lanes` itself (640) and keeps the pad
    lanes zero, so the layout stays the one everybody sees. Any new
    user of the slab follows the same rule: write rows
    `pages.at[layer, page, offset].set(row)`, read pages
    `pages[layer, page]` (or hand the whole slab and a static layer to
    the family's kernel) — never reshape the slab itself; split the row
    or the gathered context.
    The jitted step scatters one token row per active slot per dispatch
    and reads each slot's table-worth back as its attention context.
    valid (cache.validity): [P, G] float32 — 1.0 where a real (non-pad,
    active) token was written; multiplied into the attention bias so
    null/stale positions read as masked, not as garbage.

    kv_dtype="int8" (cache.sidecars only; a family without them is
    refused by name) stores the planes as int8 with per-page SYMMETRIC
    scales (the PR-7 EFInt8 convention: scale = amax/127, value =
    q * scale) in [L, P] float32 sidecars, one per plane. The sidecars
    exist in both modes (all-zero and inert under "f32") so the
    decode/prefill step signatures — and therefore the two-compile pin
    — are identical across kv dtypes. Scales ride every page lifecycle
    event with their page: copy-on-write duplicates them in the same
    dispatch, prefix hits share them (the page id indexes both slab and
    sidecar), and eviction/drop_generation need no device work — a
    reused page's first write (offset 0) resets its scale on device.

    Per-slot state (cache.slot_state): one array `[layers, slots,
    *shape]` for each declaration, after everything paged. It belongs
    to a slot, not to a page: the allocator, the tables, copy-on-write
    and the prefix cache never see it.

    `state` is all of it as the programs take and return it (donated):
    the planes, then the sidecars, then the validity plane, then the
    per-slot state arrays; `state_names` names them in that order. k,
    v, k_scale, v_scale and valid name the parts of a two-plane cache.
    """

    def __init__(self, geom: PageGeometry, cache=None, heads: int = 0,
                 head_dim: int = 0, dtype=jnp.bfloat16,
                 kv_dtype: str = "f32", layers: int = 0):
        if not isinstance(cache, CacheSpec):
            # the per-head K/V cache by its sizes (layers, heads,
            # head_dim, dtype): what this class took before families
            # declared their caches
            cache = CacheSpec(layers=int(cache or layers), planes=2,
                              lanes=heads * head_dim, dtype=dtype,
                              sidecars=True, validity=True)
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"serve kv_dtype must be one of {KV_DTYPES}, "
                f"got {kv_dtype!r}")
        self.geom = geom
        self.cache = cache
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        if self.quantized and not cache.sidecars:
            raise ValueError(
                "this model family's cache declares no int8 scale "
                "sidecars: kv_dtype 'int8' cannot be served for it")
        shape = (cache.layers, geom.pages, geom.page, cache.width)
        store = jnp.int8 if self.quantized else cache.dtype
        state = [jnp.zeros(shape, store) for _ in range(cache.planes)]
        if cache.sidecars:
            state += [jnp.zeros((cache.layers, geom.pages), jnp.float32)
                      for _ in range(cache.planes)]
        if cache.validity:
            state.append(jnp.zeros((geom.pages, geom.page), jnp.float32))
        # per-slot state, indexed by slot and never by page: zeroed here
        # for a defined start only, a program zeroes a slot's own where
        # its stream begins (position 0)
        state += [jnp.zeros((st.layers, geom.slots) + tuple(st.shape),
                            st.dtype) for st in cache.slot_state]
        self.state = tuple(state)
        self.state_names = tuple(
            [f"plane_{i}" for i in range(cache.planes)]
            + ([f"scale_{i}" for i in range(cache.planes)]
               if cache.sidecars else [])
            + (["valid"] if cache.validity else [])
            + [st.name for st in cache.slot_state])

    # the parts of a two-plane cache with sidecars and validity (GPT's)
    k = property(lambda self: self.state[0])
    v = property(lambda self: self.state[1])
    k_scale = property(lambda self: self.state[2])
    v_scale = property(lambda self: self.state[3])
    valid = property(lambda self: self.state[4])

    @property
    def device_bytes(self) -> int:
        return int(sum(a.nbytes for a in self.state))

    @property
    def decode_bytes_per_token(self) -> int:
        """Deterministic HBM bytes-per-decoded-token proxy (the PR-7
        comm-proxy discipline: computed from page geometry + dtype,
        never timers, so decode-bandwidth regressions stay assertable
        on the CPU tier, with no chip attached).

        One decode dispatch row reads the slot's whole context through
        the page table (every plane, every layer), writes one token row
        back, and in int8 mode additionally moves the per-page scale
        sidecars — so per decoded token, for a cache of N planes:

            L * (N*(C+1)*lanes*itemsize  [context read + row write]
                 + int8? N*4*(Pmax+1))   [scale reads + scale write]

        The int8/f32 ratio is ~itemsize(f32)/1 (~4x for f32 models,
        the bench arm's >= 3.5x self-assert).
        """
        cache = self.cache
        per_layer = cache.planes * (self.geom.context + 1) * cache.lanes \
            * self.state[0].dtype.itemsize
        if self.quantized:
            per_layer += cache.planes * 4 * (self.geom.pages_per_slot + 1)
        return int(cache.layers * per_layer)


class PageAllocator:
    """Refcounted host allocator over pages 1..P-1 (page 0 reserved null)
    with an optional prefix-cache layer (module docstring for the page
    state machine).

    alloc() returns the lowest free id (deterministic — the bit-identity
    tests replay the same allocation sequence), falls back to evicting
    the LRU unreferenced cached page, and returns None only when every
    page is actively referenced; the engine turns None into a slot
    STALL, never an error, and sheds load before stalls can deadlock.

    Every page handed to a slot carries one reference; sharing a cached
    page via lookup_prefix() adds one more. free() drops exactly one
    reference per listed page — the engine's release path does not know
    (or need to know) which pages are shared.
    """

    def __init__(self, geom: PageGeometry):
        self.geom = geom
        # pop() takes from the tail; store descending so ids come out 1, 2, …
        self._free: List[int] = list(range(geom.pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}          # pid -> refcount (>= 1)
        # the prefix cache is PARTITIONED by serving-weight generation:
        # KV bytes are a function of the weights that produced them, so a
        # chain-hash match under different weights is NOT the same cache
        # entry. Registration/lookup key on (generation, chain hash);
        # a hot-swap retires a whole partition via drop_generation().
        self._hash_of: Dict[int, tuple] = {}     # pid -> (gen, chain hash)
        self._by_hash: Dict[tuple, int] = {}     # (gen, chain hash) -> pid
        # refcount-0 registered pages, oldest first (eviction order)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.evictions = 0

    # ------------------------------------------------------------ allocation
    def alloc(self) -> Optional[int]:
        if self._free:
            pid = self._free.pop()
        elif self._lru:
            # revivable but unreferenced: the cheapest page to sacrifice
            pid, _ = self._lru.popitem(last=False)
            self._unregister(pid)
            self.evictions += 1
        else:
            return None
        self._refs[pid] = 1
        return pid

    def free(self, page_ids: Sequence[int]) -> None:
        """Drop ONE reference per listed page. A page whose refcount
        reaches 0 returns to the free list — unless it is registered in
        the prefix cache, in which case it parks in the LRU with its
        contents intact, awaiting a hit or eviction.

        The re-sort below makes alloc/free an exact involution:
        granting N pages and freeing them back restores the free list
        bit-for-bit, order included. The speculative-decode rollback
        (engine._dispatch_spec) leans on this — pre-granting a verify
        window's page tail and trimming the rejected part leaves the
        allocator exactly where a never-proposed run leaves it."""
        released = False
        for pid in page_ids:
            pid = int(pid)
            if not 0 < pid < self.geom.pages:
                raise ValueError(f"freeing page {pid} outside slab "
                                 f"(1..{self.geom.pages - 1})")
            if pid not in self._refs:
                raise ValueError(f"double free of page {pid}")
            self._refs[pid] -= 1
            if self._refs[pid] > 0:
                continue
            del self._refs[pid]
            if pid in self._hash_of:
                self._lru[pid] = None      # newest at the end
            else:
                self._free.append(pid)
                released = True
        if released:
            # keep lowest-id-first allocation after churn (determinism)
            self._free.sort(reverse=True)

    # ---------------------------------------------------------- prefix cache
    def register_prefix(self, pid: int, digest: bytes,
                        gen: int = 0) -> bool:
        """Publish a referenced, fully-written prompt page under its
        chain hash, in the partition of the weight generation whose
        forward pass produced its KV bytes. Returns False (no-op) when
        the (generation, hash) key is already mapped — first writer
        wins; the duplicate page stays a private unregistered page."""
        if pid not in self._refs:
            raise ValueError(f"registering unreferenced page {pid}")
        key = (int(gen), digest)
        if key in self._by_hash or pid in self._hash_of:
            return False
        self._hash_of[pid] = key
        self._by_hash[key] = pid
        return True

    def lookup_prefix(self, digest: bytes, gen: int = 0) -> Optional[int]:
        """Prefix-cache hit WITHIN the given weight generation's
        partition: take one reference on the page registered under
        (gen, digest), reviving it from the LRU if it was parked there.
        Returns None on miss — a page cached under different weights is
        never a hit, no matter the token match."""
        pid = self._by_hash.get((int(gen), digest))
        if pid is None:
            return None
        self._lru.pop(pid, None)
        self._refs[pid] = self._refs.get(pid, 0) + 1
        return pid

    def drop_generation(self, gen: int) -> int:
        """Retire a weight generation's whole cache partition (hot-swap
        cleanup once its last stream detached): unregister every page in
        the partition; parked (refcount-0) ones go straight back to the
        free list. Returns the number of pages unregistered."""
        gen = int(gen)
        victims = [pid for pid, (g, _) in self._hash_of.items() if g == gen]
        released = False
        for pid in victims:
            self._unregister(pid)
            if pid in self._refs:
                continue  # frees normally when its last stream releases
            if pid in self._lru:
                del self._lru[pid]
            self._free.append(pid)
            released = True
        if released:
            self._free.sort(reverse=True)
        return len(victims)

    def writable(self, pid: int) -> bool:
        """True when a slot may scatter into the page in place: exactly
        one reference and not published in the prefix cache. A shared or
        registered page must be copy-on-write split first — another slot
        (or a future cache hit) reads those bytes."""
        return self._refs.get(pid, 0) == 1 and pid not in self._hash_of

    def refcount(self, pid: int) -> int:
        return self._refs.get(int(pid), 0)

    def _unregister(self, pid: int) -> None:
        digest = self._hash_of.pop(pid, None)
        if digest is not None:
            self._by_hash.pop(digest, None)

    # ------------------------------------------------------------ accounting
    def conserved(self) -> bool:
        """Page conservation by the lists' LENGTHS alone, in constant
        time: null + free + referenced + parked == every page, and the
        hash index as long as its inverse. What a leaked release path
        breaks; check_invariants() says what else is wrong, in time
        proportional to the slab (4 ms at 49,153 pages)."""
        return (1 + len(self._free) + len(self._refs) + len(self._lru)
                == self.geom.pages
                and len(self._by_hash) == len(self._hash_of))

    def check_invariants(self) -> List[str]:
        """Audit the three-state pool; returns human-readable violation
        strings (empty = healthy). The load-bearing identity is page
        conservation — null + free + referenced + parked-LRU == every
        page — which is exactly what a leaked release path breaks
        (a page referenced by nobody yet on no list is gone until
        restart). The engine runs this on every release and after
        supervisor recovery: raises in strict mode (tests, bench),
        counts kubeml_serve_page_leaks_total in production
        (strict_pager=False, wired by control/ps.py)."""
        problems: List[str] = []
        free, refd = set(self._free), set(self._refs)
        parked = set(self._lru)
        if len(free) != len(self._free):
            problems.append("free list holds duplicate page ids")
        for name, ids in (("free", free), ("referenced", refd),
                          ("parked", parked)):
            bad = [p for p in ids if not 0 < p < self.geom.pages]
            if bad:
                problems.append(f"{name} pages outside slab: {bad}")
        for a, b in (("free", "referenced"), ("free", "parked"),
                     ("referenced", "parked")):
            inter = {"free": free, "referenced": refd,
                     "parked": parked}[a] & \
                    {"free": free, "referenced": refd, "parked": parked}[b]
            if inter:
                problems.append(f"pages both {a} and {b}: {sorted(inter)}")
        accounted = 1 + len(free) + len(refd) + len(parked)
        if accounted != self.geom.pages:
            problems.append(
                f"page conservation broken: null(1) + free({len(free)}) "
                f"+ referenced({len(refd)}) + parked({len(parked)}) "
                f"= {accounted}, slab has {self.geom.pages}")
        if any(c < 1 for c in self._refs.values()):
            problems.append("refcount below 1 retained in _refs")
        # hash index must be a bijection, and every parked page must be
        # registered (an unregistered refcount-0 page belongs on the
        # free list, not the LRU)
        if len(self._by_hash) != len(self._hash_of):
            problems.append("prefix-hash index is not a bijection")
        for pid, key in self._hash_of.items():
            if self._by_hash.get(key) != pid:
                problems.append(
                    f"hash index mismatch for page {pid}")
        unreg = parked - set(self._hash_of)
        if unreg:
            problems.append(f"parked pages not registered: {sorted(unreg)}")
        return problems

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def evictable_pages(self) -> int:
        """Cached (registered, refcount-0) pages alloc() may evict."""
        return len(self._lru)

    @property
    def cached_pages(self) -> int:
        """Pages registered in the prefix cache (referenced or parked)."""
        return len(self._hash_of)

    @property
    def in_use(self) -> int:
        """Pages some slot currently references. Cached-but-unreferenced
        pages are reclaimable on demand, so they do not count."""
        return len(self._refs)

    def utilization(self) -> float:
        return self.in_use / self.geom.usable_pages
