"""ServeEngine — continuous batching over a paged KV pool.

Scheduler design (Orca, OSDI '22, over PagedAttention's page pool,
SOSP '23):

- **slots and pages**: the family's ``init_paged_cache`` holds ONE
  pool of fixed-size KV pages and ``max_slots`` per-slot
  ``lengths``/``tokens``/``rngs`` vectors. A slot's sequence lives in
  the pages its host-side page-table row names (:class:`PageAllocator`
  hands them out, page 0 is scratch), so admission is bounded by free
  PAGES, and read-only prefix pages are shared between slots
  (:class:`PrefixCache`, copy-on-write through ``copy_page``).
  Per-slot ``lengths`` confine attention to each request's own prefix.
  A family none of whose state grows with tokens (retention: a fixed
  block a slot) gets the same bank with a pool of no pages: admission
  is bounded by free SLOTS, page-table rows are empty, and
  ``kv_cache_stats()`` reports ``pages_total`` 0 and the block as
  ``state_bytes_per_slot``.
- **admission at step boundaries**: a free slot is seated by the next
  queued request via a per-BUCKET prefill program (the prompt's
  unshared suffix end-padded to a power of two — exact, see
  ``llama.prefill_slot_paged``), so prefill compilations are bounded
  by the bucket count.
- **one decode program**: every step runs ``decode_slots_paged`` over
  every slot; per-slot position/length/rng/sampling vectors and the
  page table (a small int32 operand) make request churn invisible to
  the compiled shape. The engine asserts this via
  :attr:`compile_count`.
- **a step yields 0..W tokens a slot**: one token (the plain step), the
  accepted run of a speculative verify (1..k+1), or nothing or a block
  (a block-diffusion family's ``block_step_slots_paged``: a denoise pass
  emits nothing, or the block's new tokens if it took its last mask). The device
  tells, beside the tokens, which of them each slot emitted, and a
  slot's length grew by as many; ``_process`` is the one path that
  mirrors the lengths and emits, at the readback the next step
  overlaps. A
  request's first token is whichever emission comes first (a
  block-diffusion prefill yields none), and TTFT is observed there.
- **overlapped host sync**: the classic serving-latency bug is a host
  readback inside the decode loop blocking the accelerator every token
  (mxlint MXL004 flags the pattern). Here step ``t``'s tokens are read
  back only AFTER step ``t+1`` has been dispatched, so the sync runs
  under the next step's device time (``MXTPU_SERVE_OVERLAP=0`` forces
  the naive synchronous order, e.g. for latency debugging).

Determinism contract: each slot's forward and sampling depend only on
its own pages and rng chain, so the engine's output for a request
never depends on how requests are interleaved, admitted, or delayed
(tested across slot counts and overlap modes). Against per-request
``llama.generate`` the math is identical and the rng chain replays
exactly; tokens are bit-identical in f32 (the tier-1 acceptance gate).
In reduced precision (bf16) the two attention formulations round
differently (the slot kernel accumulates in f32; the scalar-pos path
casts probs to the compute dtype), so a near-tie token can differ —
batch-size-invariance, not cross-kernel bit-equality, is the contract
there.
"""
from __future__ import annotations

import heapq
import itertools
from collections import deque
import os
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from .. import telemetry
from ..telemetry import distributed as dtrace
from ..models import serving_family
from ..ops.threshold import thresholds_path

__all__ = ["Request", "KVHandoff", "ServeEngine", "bucket_for",
           "resume_key", "PageAllocator", "PrefixCache",
           "ngram_drafter"]

# admission wait is measured in engine steps (arrival → slot grant)
_WAIT_STEP_BUCKETS = (0.0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 1024)


_engine_seq = itertools.count(1)     # atomic: engines build on threads


def _engine_metrics(eid: str, attention: Dict[str, str], sampler: str):
    """Process-wide serve metrics (one handle set per engine; the
    registry interns children, so every engine shares the TOTALS).
    Point-in-time gauges are labelled per engine instead — two live
    engines sharing one queue-depth gauge would just overwrite each
    other. Created at engine construction — the telemetry knob is
    read then."""
    return {
        "requests": telemetry.counter(
            "serve_requests_total", "Requests submitted to ServeEngine"),
        "tokens": telemetry.counter(
            "serve_tokens_total", "Tokens emitted by ServeEngine"),
        "steps": telemetry.counter(
            "serve_steps_total", "Decode steps dispatched"),
        # the same steps by what their program's attention was built on
        # (static per compiled program; ``attention`` maps "plain" and
        # "verify" to the family's word for each) and by how their
        # sampler finds its two thresholds
        **{"steps_" + step: telemetry.counter(
            "serve_decode_steps_total",
            "Decode steps dispatched, by the attention their program "
            "was built with: pages (the kernel reads live pages out of "
            "the pool), gathered (every slot's whole row copied out), "
            "state_kernel or state (a retention step over a fixed state "
            "a slot: one Pallas kernel a layer, or the jnp form); and by "
            "its sampler's threshold search: search_kernel (one Pallas "
            "kernel, the rows resident in VMEM) or search (the jnp form)",
            attention=path, sampler=sampler)
           for step, path in attention.items()},
        "queue": telemetry.gauge(
            "serve_queue_depth", "Requests queued, not yet admitted",
            engine=eid),
        "slots": telemetry.gauge(
            "serve_slot_occupancy", "Active slots in the decode bank",
            engine=eid),
        "wait": telemetry.histogram(
            "serve_admission_wait_steps",
            "Engine steps between a request's arrival and its slot",
            buckets=_WAIT_STEP_BUCKETS),
        "latency": telemetry.histogram(
            "serve_token_latency_ms",
            "Inter-token gaps per request (host emission clock)"),
        # KV occupancy: what the donated state reserves against what
        # live sequences cover (the perfscope ledger books the former)
        "kv_reserved": telemetry.gauge(
            "serve_kv_reserved_bytes",
            "Bytes the engine's donated state (page pool and fixed "
            "per-slot state) reserves", engine=eid),
        "kv_live": telemetry.gauge(
            "serve_kv_live_bytes",
            "Bytes of the donated state covered by live sequence "
            "prefixes", engine=eid),
        "kv_occ": telemetry.gauge(
            "serve_kv_occupancy_ratio",
            "live/reserved fraction of the donated state", engine=eid),
        "pages_total": telemetry.gauge(
            "serve_kv_pages_total",
            "Allocatable pages in the paged KV pool (scratch page 0 "
            "excluded)", engine=eid),
        "pages_free": telemetry.gauge(
            "serve_kv_pages_free",
            "Pages not mapped by any slot or prefix-cache entry",
            engine=eid),
        "pages_shared": telemetry.gauge(
            "serve_kv_pages_shared",
            "Pages mapped by more than one owner (refcount >= 2)",
            engine=eid),
        "prefix_hits": telemetry.counter(
            "serve_prefix_cache_hits_total",
            "Admissions seated on shared prefix pages (warm prefill)"),
        "prefix_misses": telemetry.counter(
            "serve_prefix_cache_misses_total",
            "Admissions that found no usable shared prefix"),
        "cow": telemetry.counter(
            "serve_cow_forks_total",
            "Copy-on-write page forks (private copy of a shared page)"),
        # speculative decoding (ISSUE 19): draft/accept accounting —
        # the accept RATE is the whole ballgame (a rejected draft costs
        # a wasted verify position), so both ends are counted
        "spec_proposed": telemetry.counter(
            "serve_spec_proposed_total",
            "Drafted tokens proposed to the speculative verify step"),
        "spec_accepted": telemetry.counter(
            "serve_spec_accepted_total",
            "Drafted tokens accepted (bit-exact match with the "
            "target chain)"),
        # time to first token inside the engine, split where it is
        # spent; the three are observed together, so their counts
        # agree. Distinct names, not one labelled family: a scrape
        # that sums a name over its labels must keep them apart
        "ttft_queue": telemetry.histogram(
            "serve_ttft_queue_ms",
            "submit -> picked for admission: waiting for a step "
            "boundary and a slot"),
        "ttft_admit": telemetry.histogram(
            "serve_ttft_admit_ms",
            "picked -> admission program dispatched: page plan, "
            "copy-page, key, arrays, the dispatch call"),
        "ttft_first_wait": telemetry.histogram(
            "serve_ttft_first_wait_ms",
            "dispatched -> first token on the host: the decode step "
            "in flight, the prefill, the decode step it rides behind"),
        "spec_len": telemetry.histogram(
            "serve_spec_accepted_len",
            "Tokens emitted per slot per speculative step (1 + "
            "accepted run length)",
            buckets=(0.0, 1, 2, 3, 4, 6, 8, 12, 16)),
    }


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def bucket_for(length: int, min_bucket: int, max_len: int) -> int:
    """Prefill bucket policy: the smallest power of two >= ``length``
    (floored at ``min_bucket``, capped at ``max_len``). Compilations
    are bounded by the bucket count: log2(max_len / min_bucket) + 1
    programs cover every prompt length."""
    if length > max_len:
        raise ValueError(f"prompt length {length} > max_len {max_len}")
    b = max(1, min_bucket)
    while b < length:
        b *= 2
    return min(b, max_len)


class PageAllocator:
    """Host-side refcounted allocator over the paged KV pool (the
    scheduler half of PagedAttention): pages are handed out from a free
    stack, shared read-only via :meth:`retain` (prefix sharing), and
    returned to the stack only when their last owner releases them.
    Page 0 is the SCRATCH page — never allocated, zeroed page-table
    rows alias it, redirected writes land there. ``n_pages`` 1 is the
    pool of no pages (the scratch page alone): what the engine keeps
    for a family whose state does not grow with tokens; it grants
    ``alloc(0)`` and nothing else. Pure host state; the caller
    (ServeEngine) serializes access under its own lock."""

    def __init__(self, n_pages: int):
        if n_pages < 1:
            raise ValueError(
                f"need >= 1 page (the scratch page), got {n_pages}")
        self.n_pages = int(n_pages)
        self._ref = np.zeros(self.n_pages, np.int32)
        # LIFO free stack: recently-freed pages are re-handed first
        # (their HBM is warm); page 0 is never a member
        self._free = list(range(self.n_pages - 1, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages with more than one owner (slot rows + cache entries)."""
        return int((self._ref >= 2).sum())

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh pages (refcount 1 each), or None — NEVER a
        partial grant: admission must be all-or-nothing so a request
        that cannot fully seat leaves the pool untouched."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        return out

    def retain(self, pages) -> None:
        """Add an owner to already-live pages (prefix sharing)."""
        for p in pages:
            if p == 0 or self._ref[p] < 1:
                raise ValueError(f"retain of non-live page {p}")
        for p in pages:
            self._ref[p] += 1

    def release(self, pages) -> None:
        """Drop one ownership per page; a page's last release frees it."""
        for p in pages:
            if p == 0 or self._ref[p] < 1:
                raise ValueError(f"release of non-live page {p}")
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(int(p))


@dataclass
class _PrefixEntry:
    tokens: Tuple[int, ...]     # the full registered prompt
    n_tokens: int               # positions the pages actually cover
    pages: Tuple[int, ...]      # cache-owned (retained) pages
    hits: int = 0
    last_used: int = 0


class PrefixCache:
    """LRU map of registered prompt prefixes → the pool pages holding
    their KV (RadixAttention's sharing, flat-keyed: a handful of system
    prompts dominate real traffic, so a bounded linear scan beats a
    radix tree at this scale). Entries OWN a refcount on their pages,
    so a prefix outlives the request that prefilled it; eviction (LRU,
    or on-demand when admission runs dry) releases that hold — pages
    still mapped by live slots survive via the slots' own refs."""

    def __init__(self, allocator: PageAllocator, max_entries: int = 32):
        self._alloc = allocator
        self.max_entries = int(max_entries)
        self._entries: Dict[Tuple[int, ...], _PrefixEntry] = {}
        self._tick = 0

    def __len__(self) -> int:
        return len(self._entries)

    def lookup(self, prompt) -> Tuple[Optional[_PrefixEntry], int]:
        """Longest registered prefix of ``prompt``, capped at
        ``len(prompt) - 1`` — the last prompt token ALWAYS runs through
        the forward pass (its logits seed the first sample)."""
        pl = len(prompt)
        pt = tuple(int(x) for x in prompt)
        best, best_m = None, 0
        for e in self._entries.values():
            cap = min(e.n_tokens, pl - 1)
            if cap <= best_m:
                continue
            m = 0
            while m < cap and pt[m] == e.tokens[m]:
                m += 1
            if m > best_m:
                best, best_m = e, m
        return best, best_m

    def pin(self, entry: _PrefixEntry) -> None:
        """Freshen an entry's LRU position WITHOUT counting a hit —
        the admission planner pins the matched entry before it
        allocates, so pool-pressure eviction prefers every other
        entry (a failed admission retries each step and must not
        inflate the hit stats)."""
        self._tick += 1
        entry.last_used = self._tick

    def touch(self, entry: _PrefixEntry) -> None:
        self.pin(entry)
        entry.hits += 1

    def insert(self, tokens, n_tokens: int, pages) -> _PrefixEntry:
        """Register ``pages`` as covering ``tokens[:n_tokens]``. The
        pages must already be live; the cache retains its own hold on
        them. Over-capacity inserts evict LRU first."""
        key = tuple(int(x) for x in tokens)
        old = self._entries.pop(key, None)
        if old is not None:
            self._alloc.release(old.pages)
        while len(self._entries) >= self.max_entries:
            if not self.evict_lru():
                break
        self._alloc.retain(pages)
        self._tick += 1
        e = _PrefixEntry(key, int(n_tokens),
                         tuple(int(p) for p in pages),
                         last_used=self._tick)
        self._entries[key] = e
        return e

    def evict_lru(self, skip: Optional[_PrefixEntry] = None) -> bool:
        """Drop the least-recently-used entry, releasing its page hold.
        ``skip`` exempts one pinned entry (the admission planner's
        matched prefix — evicting it mid-plan would free the very
        pages the plan is about to share). Returns False when nothing
        is evictable."""
        key, oldest = None, None
        for k, e in self._entries.items():
            if e is skip:
                continue
            if oldest is None or e.last_used < oldest:
                key, oldest = k, e.last_used
        if key is None:
            return False
        e = self._entries.pop(key)
        self._alloc.release(e.pages)
        return True

    def top(self, n: int = 5) -> List[Dict[str, Any]]:
        """The most-hit prefixes — diagnose/Grafana fodder."""
        es = sorted(self._entries.values(), key=lambda e: -e.hits)[:n]
        return [{"n_tokens": e.n_tokens, "hits": e.hits,
                 "pages": len(e.pages),
                 "head": list(e.tokens[:8])} for e in es]


@dataclass
class Request:
    """One generation request. ``temperature=0`` is greedy; ``seed``
    starts the request's OWN rng chain (the one ``generate`` would use
    as ``rng=PRNGKey(seed)``). ``arrival_step`` delays admission until
    that engine step — the hook seeded arrival streams (bench, tests)
    use. ``on_token(rid, token)`` streams tokens as they are
    produced; ``on_done(rid, reason)`` fires exactly once per request
    with reason ``"complete"``, ``"cancel"``/other explicit
    :meth:`ServeEngine.cancel` reasons, or ``"deadline"``.
    ``deadline_s`` is a RELATIVE budget on the engine's clock: a
    request still running (or still queued) that many seconds after
    ``submit`` is cancelled at the next step boundary — the gateway's
    slow-client defense (a stalled consumer must not hold a slot
    forever). ``rng``, when set, is an explicit (2,) uint32 chain
    state used INSTEAD of ``PRNGKey(seed)`` — the gateway's
    crash-recovery re-dispatch prefills ``prompt + already-streamed
    tokens`` with the chain fast-forwarded past them
    (:func:`resume_key`), so the resumed stream replays the exact
    sampling chain a fault-free run would have used. ``ctx``, when
    set, is the request's :class:`~mxtpu.telemetry.TraceContext`:
    every per-request span/instant the engine records (seat, prefill,
    finalize) carries its trace_id, so a multi-hop serving path
    stitches into one timeline."""
    prompt: Any
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0
    arrival_step: int = 0
    on_token: Optional[Callable[[int, int], None]] = None
    on_done: Optional[Callable[[int, str], None]] = None
    deadline_s: Optional[float] = None
    rng: Optional[Any] = None
    ctx: Optional[Any] = None
    # the engine's TTFT stamps (perf_counter seconds): submit, picked,
    # admission program dispatched; cleared when the first token is out
    _stamps: List[float] = field(default_factory=list, init=False,
                                 repr=False, compare=False)


def cancel_counter(reason: str):
    """``serve_cancelled_total{reason}`` — the ONE definition of the
    cancel counter; every serving layer (engine, gateway, disagg)
    increments through here so the name/help/labels cannot fork."""
    return telemetry.counter(
        "serve_cancelled_total",
        "Requests ended before completion, by reason",
        reason=reason)


@jax.jit
def _fast_forward_chain(key, n):
    """``n`` carry-half splits in ONE compiled dispatch (``n`` is a
    traced operand, so one program covers every prefix length)."""
    return jax.lax.fori_loop(
        0, n, lambda _, k: jax.random.split(k)[0], key)  # noqa: MXL301 — this IS the chain primitive resume_key replays


def resume_key(seed: int, n_emitted: int) -> np.ndarray:
    """The rng chain state of a request seeded ``seed`` after it has
    emitted ``n_emitted`` tokens: every emission (the prefill's first
    token and each decode step) consumes exactly one
    ``jax.random.split``, keeping the carry half — so re-prefilling
    ``prompt + emitted`` with this key makes token ``n_emitted + 1``
    sample from the same subkey, on the same logits, as the fault-free
    run (the engine's deterministic re-dispatch contract)."""
    key = jax.random.PRNGKey(int(seed))  # noqa: MXL301 — chain ROOT:
    n = int(n_emitted)                   # resume_key defines the oracle
    if n > 0:
        key = _fast_forward_chain(key, np.int32(n))
    return np.asarray(key, np.uint32)


def ngram_drafter(history: np.ndarray, k: int) -> np.ndarray:
    """The default model-free drafter: propose the ``k`` tokens that
    followed the most recent earlier occurrence of the history's
    longest trailing n-gram (g = 3, 2, 1 — prompt/self-repetition
    lookup, cf. "prompt lookup decoding"). A match at position ``i``
    implies the stream repeats with period ``(n - g) - i``, so when
    fewer than ``k`` tokens literally follow the match the draft is
    extended cyclically — a plateau (period 1) drafts the full budget
    instead of a single token. Deterministic pure host arithmetic:
    drafting never touches the rng chain, the device, or any
    cross-request state, so speculative runs stay bit-identical and
    re-dispatch-safe no matter what this returns. Returns up to ``k``
    int32 tokens (possibly none — a draftless step emits one token
    exactly like the plain path)."""
    h = np.asarray(history, np.int64).reshape(-1)
    n = int(h.size)
    if k < 1 or n < 2:
        return np.empty(0, np.int32)
    for g in (3, 2, 1):
        if n <= g:
            continue
        tail = h[n - g:]
        for i in range(n - g - 1, -1, -1):
            if np.array_equal(h[i:i + g], tail):
                period = (n - g) - i
                out = h[[i + g + (j % period) for j in range(k)]]
                return out.astype(np.int32)
    return np.empty(0, np.int32)


@dataclass
class KVHandoff:
    """A prefill worker's detached output — everything a decode engine
    needs to seat the request without re-running the prompt
    (``llama.prefill_detached`` produces it, ``llama.inject_paged_kv``
    consumes it). ``k``/``v``: (L, n_kv_heads, bucket, hd) host
    arrays; ``rng``: the (2,) uint32 chain state AFTER the first-token
    split, so decode continues the exact chain ``generate`` would."""
    k: np.ndarray
    v: np.ndarray
    true_len: int
    token: int
    rng: np.ndarray


@dataclass
class _Dispatch:
    """One in-flight decode step: the device handles plus the host-side
    snapshot needed to attribute its tokens after the overlapped
    sync. ``sampled`` holds W tokens a slot, row-major, with the
    family's ``STEP_COUNTS`` values behind them: (S,) of a plain step,
    (S, W) of a speculative verify, and of a block step (2 S B +
    counts,): the tokens, then 1 for each that the step emitted.
    ``emits`` (S, W) bool is the verify's say of which of a slot's W it
    emitted (None: what ``sampled`` holds). A slot's length advanced by
    what it emitted. A speculative step also carries the per-slot
    proposed-draft counts for the accept-rate accounting."""
    sampled: Any                                   # device int32
    slots: List[Tuple[int, int]]                   # (slot, rid) active
    firsts: List[Tuple[int, Any]]                  # (rid, device (0..1,))
    emits: Any = None                              # spec: device (S, W)
    proposed: Optional[np.ndarray] = None          # spec: (S,) host


@dataclass
class _PrefillJob:
    """A prompt on its way through chunked prefill: ``done`` of its
    tokens are in the stage."""
    slot: int
    rid: int
    req: "Request"
    prompt: np.ndarray
    done: int = 0


class ServeEngine:
    """Continuous-batching scheduler over one model + one KV page pool.

    Args: ``cfg``/``params`` — a config and parameter pytree of a
    family in ``models.SERVING_FAMILIES`` (llama, sambay, latent_moe,
    retention, blockdiff_moe): the engine
    takes every program it runs from that family's module, found once
    here from ``cfg.family``, and refuses at construction the options
    the family's ``SERVE_UNSUPPORTED`` names, with the mechanism in
    the way (a llama weight-only int8 tree from ``quantize_params_int8``
    rides the same programs). ``max_slots``/``max_len``/``min_bucket`` default
    from ``MXTPU_SERVE_MAX_SLOTS`` / the config's ``max_seq_len`` /
    ``MXTPU_SERVE_MIN_BUCKET``. ``mesh`` serves sharded (pool per
    ``llama.paged_cache_specs``, params as placed by the training
    rules). ``page_size`` (``MXTPU_KV_PAGE_SIZE``, 16) and ``n_pages``
    (``MXTPU_KV_PAGES``; by default every slot's ``max_len`` plus the
    scratch page) size the pool; ``prefix_cache`` shares prompt
    prefixes' pages between requests (on unless the family refuses
    it); ``int8_pages`` stores the pool as int8 with per-token scales.
    ``paged`` is what is left of a switch between this pool and a dense
    bank that is gone: ``True`` is its one legal value, kept while the
    benchmark's drivers pass it (ROADMAP D12). ``prefill_chunk`` (a
    family with ``prefill_slot_paged_chunk``: sambay, latent_moe,
    retention) prefills a
    prompt that many tokens at a time, at most one chunk between two decode steps once
    the running requests are no fewer than the waiting ones: the gap a
    running request sees beside an admission is one chunk's time
    whatever the prompt's length, and the prompt's first token comes
    that many iterations later."""

    @telemetry.setup_phase("engine_build")
    def __init__(self, cfg, params, *, max_slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 min_bucket: Optional[int] = None,
                 mesh=None, overlap: Optional[bool] = None,
                 clock: Optional[Callable[[], float]] = None,
                 paged: bool = True,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 int8_pages: Optional[bool] = None,
                 speculate_k: Optional[int] = None,
                 drafter: Optional[Callable] = None,
                 prefill_chunk: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        # the family's module: every program below comes from it
        fam = self._family = serving_family(cfg)
        self._unsupported = getattr(fam, "SERVE_UNSUPPORTED", {})
        # deadlines are measured on THIS clock (monotonic seconds);
        # injectable so deadline/autoscale tests are deterministic
        self._clock = clock or time.monotonic
        self.max_slots = (max_slots if max_slots is not None
                          else _env_int("MXTPU_SERVE_MAX_SLOTS", 8))
        self.max_len = int(max_len or cfg.max_seq_len)
        self.min_bucket = (min_bucket if min_bucket is not None
                           else _env_int("MXTPU_SERVE_MIN_BUCKET", 16))
        self.overlap = (os.environ.get("MXTPU_SERVE_OVERLAP", "1")
                        != "0") if overlap is None else bool(overlap)
        # the engine's name in per-request trace events (EngineReplica
        # overwrites it with the replica name, so a request that moves
        # replicas shows WHICH bank served each segment)
        self.role = "engine"
        # model-build tag (fleet pools stamp this with the pool's
        # checkpoint version at spawn): joins the role in trace
        # events, so a timeline spanning a hot-swap shows which BUILD
        # served each segment, not just which replica
        self.build: Optional[str] = None

        # KV lives in a fixed page pool (PagedAttention) with
        # host-owned per-slot page tables; admission is bounded by
        # free PAGES, not slots, and prefix pages are shared CoW
        if paged is not True:
            raise ValueError(
                f"paged={paged!r}: the engine has one KV bank, the page "
                "pool (page_size, n_pages); the dense slot bank went in "
                "PR 29. Leave the keyword out")
        self.page_size = int(page_size
                             or _env_int("MXTPU_KV_PAGE_SIZE", 16))
        self._pages_per_slot = -(-self.max_len // self.page_size)
        # default pool: every slot can grow to max_len, plus scratch
        self.n_pages = int(n_pages or _env_int(
            "MXTPU_KV_PAGES",
            self.max_slots * self._pages_per_slot + 1))
        self.prefix_cache_enabled = (
            prefix_cache if prefix_cache is not None
            else "prefix_cache" not in self._unsupported
            and os.environ.get("MXTPU_KV_PREFIX_CACHE", "1") != "0")
        self.int8_pages = (
            bool(int8_pages) if int8_pages is not None
            else os.environ.get("MXTPU_KV_INT8_PAGES", "0") == "1")

        # speculative decoding (ISSUE 19): draft k tokens host-side
        # per slot per step, verify them in ONE batched forward
        # (decode_slots_spec), and advance each slot by its accepted
        # run length
        self.speculate_k = int(
            speculate_k if speculate_k is not None
            else _env_int("MXTPU_SERVE_SPECULATE_K", 0))
        if self.speculate_k < 0:
            raise ValueError(
                f"speculate_k must be >= 0, got {self.speculate_k}")
        self._drafter = drafter or ngram_drafter
        self._refuse({"prefix_cache": self.prefix_cache_enabled,
                      "speculate_k": self.speculate_k,
                      "int8_pages": self.int8_pages,
                      "mesh": mesh is not None})
        self.prefill_chunk = int(prefill_chunk or 0)
        if self.prefill_chunk and not hasattr(
                fam, "prefill_slot_paged_chunk"):
            raise ValueError(
                "prefill_chunk needs a family whose prefill carries "
                "its state from chunk to chunk (sambay); got "
                f"{cfg.family}")
        if self.speculate_k:
            # the host drafter conditions on every token emitted so
            # far, so the previous step's tokens must be read back
            # BEFORE the next step is drafted — speculative mode is
            # inherently synchronous, and its sync cost is amortized
            # over the whole accepted run rather than one token
            self.overlap = False

        # host time: the allocation's programs (``others`` in the build
        # catalog) and their dispatch; the device fills the pool behind
        with telemetry.setup_span("state_alloc"):
            state = fam.init_paged_cache(
                cfg, self.max_slots, self.n_pages, self.page_size,
                mesh=mesh, int8=self.int8_pages)
            # the small per-slot vectors (every family's three, or the
            # family's own list: a block-diffusion slot also holds its
            # block); everything else is the donated state (llama: the
            # K/V pools; sambay: a pool plus the fixed per-slot rings
            # and recurrent state)
            self._sv = {n: state.pop(n) for n in getattr(
                fam, "SLOT_VARS", ("lengths", "tokens", "rngs"))}
            self._kv = state
            # bytes of the donated state by kind: pages (the kinds named
            # ``*_pages``: keys and values, or latent rows) grow with the
            # tokens held; a family's other kinds are fixed blocks per slot
            kinds = getattr(fam, "STATE_KINDS", {})
            by_kind: Dict[str, int] = {}
            for n, a in state.items():
                k = kinds.get(n, "kv_pages")
                by_kind[k] = by_kind.get(k, 0) + int(a.nbytes)
        paged = sum(n for k, n in by_kind.items() if k.endswith("_pages"))
        if not paged:
            # no kind of this family's state grows with tokens
            # (retention): the pool is the scratch page alone, a free
            # slot is all an admission needs, and max_len bounds
            # positions only
            self.n_pages = 1
        # which attention the family builds the decode programs on over
        # this state ("pages" | "gathered" | "state_kernel" | "state"):
        # its choice, exported as it is
        # (serve_decode_steps_total{attention}, kv_cache_stats())
        self._attention = {
            "plain": fam.decode_attention_path(cfg, state, mesh),
            "verify": fam.decode_attention_path(cfg, state, mesh,
                                                verify=True)}
        # how the decode programs' sampler finds its two thresholds
        # over the bank's (slots, vocabulary) logits ("search_kernel" |
        # "search"): ops.threshold's choice, exported as it is
        self._sampler = thresholds_path(
            (self.max_slots, cfg.vocab_size), np.float32, mesh=mesh)
        # the kv state is donated through every program (in-place in
        # HBM); the small vectors are not, so the previous step's
        # sampled tokens stay readable during the overlapped sync.
        # watch(): ONE decode program ever — cache growth past 1 is the
        # spurious-recompile anomaly (recompile_total + offending key)
        telemetry.install_compile_listener()
        # watch_jit(): each program is compiled under the name of the
        # model function it runs, so a trace reads
        # jit_decode_slots_paged, not jit__unknown
        # a family whose step yields nothing or a block a slot brings
        # its own step program, whose tokens are followed by which of
        # them it emitted (_Dispatch); every other family's yields one
        step = getattr(fam, "block_step_slots_paged", None)
        self._block_step = step is not None
        step = step or fam.decode_slots_paged
        self._decode = telemetry.watch_jit(
            partial(step, cfg, mesh=mesh),
            "serve_decode", step.__name__, loop="serve",
            donate_argnums=(1,))
        # what the family's decode program counts on the device: the
        # values ride behind the sampled tokens in the array _process
        # reads back anyway (the family's STEP_COUNTS names the series;
        # a histogram's value is the int over its ``per``)
        self._step_counts = [
            (telemetry.histogram(c["name"], c["help"], buckets=c["buckets"]),
             c["per"]) if "buckets" in c
            else (telemetry.counter(c["name"], c["help"]), 0)
            for c in getattr(fam, "STEP_COUNTS", ())]
        self._prefills: Dict[Any, Any] = {}
        self._injects: Dict[int, Any] = {}
        self._spec_decode = None
        if self.speculate_k:
            # the ONE extra watched program speculative mode adds (the
            # k-verify step) — compile_count's bound grows by exactly
            # this; steps where no slot has a draft still run the
            # plain decode program (mixed stepping, same pool)
            self._spec_decode = telemetry.watch_jit(
                partial(fam.decode_slots_spec, cfg, mesh=mesh),
                "serve_spec_verify", "decode_slots_spec", loop="serve",
                donate_argnums=(1,))
        # host page-table (a small int32 operand per step), the
        # refcounted allocator, the prefix cache, and the CoW
        # fork program (ONE program: src/dst are traced scalars)
        self._pt = np.zeros(
            (self.max_slots, self._pages_per_slot if paged else 0),
            np.int32)
        self._pages = PageAllocator(self.n_pages)
        self._prefix = (PrefixCache(self._pages)
                        if self.prefix_cache_enabled else None)
        # a per-engine wrapper (NOT the bare copy_page, which
        # watch_jit's partial is): jit caches key on callable
        # identity, so a shared function would alias cache sizes
        # across engines and skew both the recompile watcher and
        # compile_count's churn gate
        self._copy_fn = telemetry.watch_jit(
            fam.copy_page, "serve_copy_page", "copy_page",
            donate_argnums=(0,))
        # engine-local tallies (the telemetry counters are
        # process-wide totals shared across engines)
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._cow_forks = 0
        # chunked prefill: the prompts being prefilled, in order of
        # admission (the head's chunks run first), the stage the head's
        # chunks hand on through (one prompt at a time, outside the
        # pool), and which slots are seated but not yet running
        self._jobs: Deque[_PrefillJob] = deque()
        self._prefilling = np.zeros(self.max_slots, bool)
        self._stage = None
        if self.prefill_chunk:
            self._stage = fam.init_prefill_stage(
                cfg, self._pages_per_slot * self.page_size,
                self.prefill_chunk)
        eid = str(next(_engine_seq))
        self.engine_id = eid
        self._m = _engine_metrics(eid, self._attention, self._sampler)
        # what a family states once about its step (a block's length, ..)
        for name, text, value in getattr(fam, "serve_gauges",
                                         lambda cfg: ())(cfg):
            telemetry.gauge(name, text, engine=eid).set(value)
        self._m_cancel: Dict[str, Any] = {}    # per-reason counters
        # span factories pre-bind their registry histograms — the
        # per-step/per-admission hot paths must not re-intern handles.
        # The five phase spans cover the engine thread's iteration
        # (their histograms' sums add to the loop's wall time); they
        # are entered every step, so they stay out of the flight ring
        self._span_sweep = telemetry.span_factory(
            "serve.sweep_pick", flight=False)
        self._span_admit = telemetry.span_factory(
            "serve.admit", flight=False)
        self._span_decode = telemetry.span_factory(
            "serve.decode_step", "serve_decode_dispatch", flight=False)
        self._span_readback = telemetry.span_factory(
            "serve.readback", flight=False)
        self._span_emit = telemetry.span_factory(
            "serve.emit", flight=False)
        self._span_prefill = telemetry.span_factory(
            "serve.prefill", "serve_prefill")
        # private resettable latency stats (always-on Histogram
        # instance, independent of the global telemetry knob)
        self._lat = telemetry.Histogram(telemetry.LATENCY_MS_BUCKETS)
        self._last_tok: Dict[int, float] = {}

        S = self.max_slots
        self._active = np.zeros(S, bool)
        self._temps = np.zeros(S, np.float32)
        self._topks = np.full(S, cfg.vocab_size, np.int32)
        self._topps = np.ones(S, np.float32)
        self._slot_rid: List[Optional[int]] = [None] * S
        # speculative mode: per-slot token history (prompt + every
        # emitted token) the host drafter conditions on, plus the
        # engine-local draft/accept tallies (all written under _lock)
        self._hist: List[List[int]] = [[] for _ in range(S)]
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_steps = 0

        # KV occupancy accounting: host-mirrored per-slot lengths (a
        # prefill seats the prompt length; every step's readback adds
        # what the device says the slot emitted — the device's
        # `lengths` vector a step behind (a block-diffusion slot's plus
        # the prompt's remainder, which waits in its first block),
        # tracked WITHOUT reading it back: a device sync here would
        # block the decode loop every token, MXL004). Reserved bytes
        # count the pool's global logical size across the mesh.
        self._slot_len = np.zeros(S, np.int64)
        for k, nbytes in by_kind.items():
            telemetry.gauge(
                "serve_state_bytes", "Bytes of the engine's donated "
                "device state, by kind: kv_pages, latent_pages, "
                "window_ring, ssm, retention_state",
                engine=eid, kind=k).set(nbytes)
        # reserved counts everything donated (the scratch page too — it
        # is real HBM); per-token bytes are the pages' over the tokens
        # they can hold (scale planes included in int8 mode; 0 where
        # the state has no pages), per-slot bytes the fixed kinds' over
        # the slots
        self._kv_reserved = sum(by_kind.values())
        self._kv_tok_bytes = paged // (self.n_pages * self.page_size)
        self._slot_state_bytes = ((self._kv_reserved - paged)
                                  // self.max_slots)
        self._m["pages_total"].set(self.n_pages - 1)
        self._m["pages_free"].set(self._pages.free_pages)
        self._m["pages_shared"].set(0)
        self._m["kv_reserved"].set(self._kv_reserved)
        self._m["kv_live"].set(0)
        self._m["kv_occ"].set(0.0)
        from ..telemetry import perfscope
        perfscope.ledger().account_tree("params", params,
                                        name=f"engine{eid}")
        perfscope.ledger().account(
            "kv_page_pool", self._kv_reserved, name=f"engine{eid}")

        # batch mode (run()) returns the per-request token lists, so
        # it must retain them; a long-lived gateway replica must NOT —
        # EngineReplica flips this off so request bookkeeping is
        # pruned at finalize instead of growing for the process life
        self.retain_results = True
        self._queue: List[Tuple[int, int, Request]] = []   # heap
        self._requests: Dict[int, Request] = {}
        self._results: Dict[int, List[int]] = {}
        self._done: Dict[int, bool] = {}
        self._handoffs: Dict[int, KVHandoff] = {}
        self._cancelled: Dict[int, str] = {}   # rid -> pending reason
        self._deadlines: Dict[int, float] = {}  # rid -> absolute clock
        self._ended: Dict[int, str] = {}       # rid -> final reason
        self._next_rid = 0
        self._step_idx = 0
        self.steps_run = 0
        # submit()/cancel() may run on gateway threads while the
        # engine loop steps; the lock guards the request-table state,
        # the condition wakes an idle run_forever on new work
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)

    # -- submission ----------------------------------------------------------
    def submit(self, request: Request) -> int:
        """Queue a request; returns its id. Validation mirrors
        ``generate``'s. Thread-safe (gateway threads submit while the
        engine loop runs)."""
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got "
                f"{request.max_new_tokens}")
        if self._positions(prompt.size,
                           request.max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds max_len "
                f"{self.max_len}")
        self._refuse({"resume_key": request.rng is not None})
        if request.top_k is not None and request.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {request.top_k}")
        if request.top_p is not None and not 0.0 < request.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {request.top_p}")
        return self._enqueue(request)

    def _positions(self, prompt_len: int, max_new_tokens: int) -> int:
        """How many positions of its pages a request can write: the
        family's answer (a block-diffusion step writes whole blocks), or
        every token once."""
        fn = getattr(self._family, "positions_written", None)
        total = int(prompt_len) + int(max_new_tokens)
        return total if fn is None else int(fn(self.cfg, int(prompt_len),
                                               int(max_new_tokens)))

    def _refuse(self, asked: Dict[str, Any]) -> None:
        """Raise for the first option in ``asked`` that is set and that
        the family's ``SERVE_UNSUPPORTED`` names, with its reason."""
        for option, on in asked.items():
            if on and option in self._unsupported:
                raise ValueError(
                    f"{option} is not supported for the "
                    f"{self.cfg.family} family: "
                    f"{self._unsupported[option]}")

    def submit_prefilled(self, handoff: KVHandoff,
                         request: Request) -> int:
        """Queue a request whose prompt was already prefilled on a
        prefill worker (disaggregated mode): admission seats the
        handed-off KV block via ``llama.inject_paged_kv`` instead of
        running a prefill program, and the worker-sampled first token
        is emitted as this request's first token."""
        self._refuse({"submit_prefilled": True})
        if handoff.true_len < 1:
            raise ValueError("empty handoff")
        if handoff.true_len + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({handoff.true_len}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds max_len "
                f"{self.max_len}")
        if handoff.k.shape[2] > self.max_len:
            raise ValueError(
                f"handoff bucket {handoff.k.shape[2]} exceeds max_len "
                f"{self.max_len}")
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if prompt.size > handoff.true_len:
            # journaled-page resume: prompt = original +
            # already-emitted tokens; admission injects the journaled
            # pages and warm-prefills ONLY the emitted suffix — no
            # prefill-worker round trip, same rng chain (resume_key)
            if prompt.size + request.max_new_tokens > self.max_len:
                raise ValueError(
                    f"prompt ({prompt.size}) + max_new_tokens "
                    f"({request.max_new_tokens}) exceeds max_len "
                    f"{self.max_len}")
        return self._enqueue(request, handoff=handoff)

    def _enqueue(self, request: Request,
                 handoff: Optional[KVHandoff] = None) -> int:
        request._stamps = [time.perf_counter()]
        with self._cv:
            rid = self._next_rid
            self._next_rid += 1
            self._requests[rid] = request
            self._results[rid] = []
            self._done[rid] = False
            if handoff is not None:
                self._handoffs[rid] = handoff
            if request.deadline_s is not None:
                self._deadlines[rid] = (self._clock()
                                        + float(request.deadline_s))
            heapq.heappush(self._queue,
                           (int(request.arrival_step), rid, request))
            self._m["requests"].inc()
            self._m["queue"].set(len(self._queue))
            self._cv.notify_all()
        return rid

    # -- cancellation / deadlines --------------------------------------------
    def cancel(self, rid: int, reason: str = "cancel") -> bool:
        """Request cancellation: the rid's slot is freed at the NEXT
        step boundary (a queued rid is finalized without ever taking a
        slot) and ``serve_cancelled_total{reason}`` increments. Returns
        False if the rid is unknown or already finished."""
        with self._cv:
            if rid not in self._requests or rid in self._ended \
                    or self._done.get(rid):
                return False
            self._cancelled.setdefault(rid, reason)
            self._cv.notify_all()
        return True

    def _cancel_counter(self, reason: str):
        m = self._m_cancel.get(reason)
        if m is None:
            m = self._m_cancel[reason] = cancel_counter(reason)
        return m

    def _finalize(self, rid: int, reason: str) -> None:
        """Exactly-once request teardown (lock held): final reason,
        cancel accounting, the on_done callback, and — with
        ``retain_results`` off — pruning, so a forever-serving replica
        stays O(live requests), not O(all requests ever)."""
        if rid in self._ended:
            return
        self._ended[rid] = reason
        self._done[rid] = True
        self._deadlines.pop(rid, None)
        self._handoffs.pop(rid, None)
        self._last_tok.pop(rid, None)
        # always pruned: a stale entry here would also permanently
        # defeat _sweep_cancelled's empty-dict fast path
        self._cancelled.pop(rid, None)
        if reason != "complete":
            self._cancel_counter(reason).inc()
            telemetry.flight().record("serve", "cancelled", rid=rid,
                                      reason=reason)
        req = self._requests[rid]
        if req.ctx is not None:
            with dtrace.use(req.ctx):
                telemetry.instant("serve.done", reason=reason,
                                  role=self.role, build=self.build)
        if req.on_done is not None:
            req.on_done(rid, reason)
        if not self.retain_results:
            self._requests.pop(rid, None)
            self._results.pop(rid, None)
            self._done.pop(rid, None)
            if rid in self._slot_rid:
                # seated: its heap entry was consumed at admission, so
                # nothing else will reap the tombstone
                self._ended.pop(rid, None)
            # a queued rid's tombstone stays until _admit pops its
            # heap entry (it must not be re-admitted)

    def _sweep_cancelled(self) -> None:
        """Lock held, once per loop: expire deadlines, and finalize
        cancelled rids that hold NO slot (queued ones — active ones
        free their slot in ``_process``, the step boundary)."""
        if self._deadlines:
            now = self._clock()
            for rid, dl in list(self._deadlines.items()):
                if now >= dl and rid not in self._ended:
                    self._cancelled.setdefault(rid, "deadline")
        if not self._cancelled:
            return
        seated = set(r for r in self._slot_rid if r is not None)
        for rid, reason in list(self._cancelled.items()):
            if rid not in seated:
                self._finalize(rid, reason)

    # -- admission -----------------------------------------------------------
    # Two phases: PICK under the engine lock (queue pops + slot
    # seating + gauges — everything submit()/cancel()/load() observe),
    # then the prefill/inject PROGRAMS outside it — a first-use bucket
    # compile takes seconds on real configs, and holding the lock
    # through it would stall every submitter and the gateway's
    # routing/scrape paths behind one admission.
    def _pick_admissions(self) -> List[Tuple[int, int, Request,
                                             Optional[KVHandoff],
                                             Dict]]:
        picks: List[Tuple[int, int, Request,
                          Optional[KVHandoff], Dict]] = []
        while self._queue:
            arrival, rid, req = self._queue[0]
            if rid in self._ended:         # cancelled while queued
                heapq.heappop(self._queue)
                if not self.retain_results:
                    self._ended.pop(rid, None)   # tombstone reaped
                continue
            if arrival > self._step_idx:
                break
            free = np.flatnonzero(~self._active)
            if free.size == 0:
                break
            # admission is bounded by free PAGES: plan the slot's
            # table row (shared prefix + CoW fork + fresh pages)
            # before committing; a pool too full to seat the head
            # request leaves it QUEUED (backpressure, never a crash)
            # — completions free pages and retry
            plan = self._plan_pages(req, self._handoffs.get(rid))
            if plan is None:
                break
            heapq.heappop(self._queue)
            req._stamps.append(time.perf_counter())
            slot = int(free[0])
            self._m["wait"].observe(max(0, self._step_idx - arrival))
            self._seat(slot, rid, req)
            self._pt[slot, :] = 0
            row = plan["row"]
            self._pt[slot, :len(row)] = row
            if req.ctx is not None:
                # once per admission, not per token: the timeline's
                # "which bank, which slot, when" anchor for this hop
                with dtrace.use(req.ctx):
                    telemetry.instant("serve.seat", slot=slot,
                                      role=self.role)
            picks.append((slot, rid, req,
                          self._handoffs.pop(rid, None), plan))
        self._m["queue"].set(len(self._queue))
        self._m["slots"].set(int(self._active.sum()))
        self._m["pages_free"].set(self._pages.free_pages)
        self._m["pages_shared"].set(self._pages.shared_pages)
        return picks

    # -- admission planning (lock held) --------------------------------
    def _alloc_with_evict(self, n: int,
                          keep: Optional[_PrefixEntry] = None
                          ) -> Optional[List[int]]:
        """All-or-nothing page grant; when the pool runs dry, evict
        prefix-cache entries LRU-first (their pages come back the
        moment no live slot shares them) and retry. ``keep`` is the
        plan's matched prefix entry — never evicted by its own
        admission."""
        while True:
            pages = self._pages.alloc(n)
            if pages is not None:
                return pages
            if self._prefix is None \
                    or not self._prefix.evict_lru(skip=keep):
                return None

    def _plan_pages(self, req: Request,
                    handoff: Optional[KVHandoff]) -> Optional[Dict]:
        """Plan one paged admission: how many pages, which are shared
        from the prefix cache, where the CoW fork goes, and what gets
        registered after prefill. Returns None on page exhaustion
        (request stays queued). Mutates ONLY the allocator/prefix
        cache (under the engine lock); the device work happens later
        in ``_run_admissions``."""
        if not self._kv_tok_bytes:
            # a token holds no bytes of this family's state: the free
            # slot the caller found is the whole grant (the family
            # refuses prefix cache and hand-off, which plan pages)
            return {"row": np.zeros(0, np.int32), "prefix_len": 0,
                    "fork": None, "register": None,
                    "ignore_handoff": False}
        ps = self.page_size
        cap = self._pages_per_slot * ps
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        total = self._positions(prompt.size, req.max_new_tokens)
        n_total = -(-total // ps)
        entry, m = None, 0
        ignore_handoff = False
        if handoff is not None:
            # the inject block spans ceil(bucket/ps) pages — pad KV
            # beyond true_len lands in slot-owned pages (length-masked)
            n_total = max(n_total,
                          self._inject_block_len(handoff) // ps)
            tl = int(handoff.true_len)
            if (prompt.size > tl
                    and tl + bucket_for(int(prompt.size) - tl,
                                        self.min_bucket,
                                        self.max_len) > cap):
                # resume suffix bucket won't fit behind the handoff —
                # fall back to a full cold prefill with the resume rng
                # (same tokens: the chain is position-, not path-,
                # dependent)
                ignore_handoff = True
                n_total = -(-total // ps)
        elif self._prefix is not None:
            entry, m = self._prefix.lookup(prompt)
            suffix_bucket = bucket_for(int(prompt.size) - m,
                                       self.min_bucket, self.max_len)
            if entry is None or m < ps or m + suffix_bucket > cap:
                # no usable share: sub-page matches aren't worth a
                # fork, and the suffix bucket must fit the row
                entry, m = None, 0
        n_shared = m // ps
        # registration: cold admissions (and warm ones the cache can't
        # already serve maximally) register the FULL prompt; a partial
        # boundary page is copied into a cache-owned page post-prefill
        # so decode writes at >= len(prompt) never touch the entry
        register = (handoff is None and self._prefix is not None
                    and int(prompt.size) >= ps
                    and m < int(prompt.size) - 1)
        reg_partial = register and (int(prompt.size) % ps != 0)
        n_fresh = n_total - n_shared
        # Pin the matched entry and retain its pages BEFORE any
        # eviction can run: under pool pressure _alloc_with_evict
        # evicts prefix entries, and without a planner hold it could
        # free (or re-hand as "fresh") the very pages this plan is
        # about to share — retain() on a dead page would kill the
        # loop, a re-handed one would alias two logical positions.
        # The holds on the full shared pages transfer to the slot's
        # row; the boundary-page hold pins the CoW fork source until
        # the copy dispatches (_prefill_into releases it).
        hold: List[int] = []
        if entry is not None:
            hold = [int(p) for p in entry.pages[:n_shared]]
            if m % ps:
                hold.append(int(entry.pages[n_shared]))
            self._pages.retain(hold)
            self._prefix.pin(entry)
        got = self._alloc_with_evict(n_fresh + (1 if reg_partial
                                                else 0), keep=entry)
        if got is None and entry is not None:
            # even with every OTHER entry evicted the warm plan does
            # not fit — drop the share and retry COLD, where the
            # matched entry itself becomes evictable (a pinned entry
            # must never wedge admission for good)
            self._pages.release(hold)
            hold, entry, m, n_shared = [], None, 0, 0
            register = (handoff is None and self._prefix is not None
                        and int(prompt.size) >= ps
                        and 0 < int(prompt.size) - 1)
            reg_partial = register and (int(prompt.size) % ps != 0)
            n_fresh = n_total
            got = self._alloc_with_evict(n_fresh + (1 if reg_partial
                                                    else 0))
        if got is None:
            if hold:
                self._pages.release(hold)   # plan abandoned: unpin
            return None
        fresh, reg_page = ((got[:-1], got[-1]) if reg_partial
                           else (got, None))
        row = np.zeros(n_total, np.int32)
        fork = None
        if entry is not None:
            row[:n_shared] = entry.pages[:n_shared]
            if m % ps:
                # the boundary page is shared but the suffix writes
                # into it — fork it into the first fresh page (the
                # planner's hold keeps the source live even if the
                # entry is evicted before the copy runs)
                fork = (int(entry.pages[n_shared]), int(fresh[0]))
            self._prefix.touch(entry)
            self._prefix_hits += 1
            self._m["prefix_hits"].inc()
        elif handoff is None and self._prefix is not None:
            self._prefix_misses += 1
            self._m["prefix_misses"].inc()
        row[n_shared:] = fresh
        reg = None
        if register:
            n_full = int(prompt.size) // ps
            reg_pages = list(row[:n_full])
            reg_copy = None
            if reg_partial:
                reg_copy = (int(row[n_full]), int(reg_page))
                reg_pages.append(int(reg_page))
            reg = {"tokens": tuple(int(t) for t in prompt),
                   "n_tokens": int(prompt.size),
                   "pages": reg_pages, "copy": reg_copy}
        return {"row": row, "prefix_len": m, "fork": fork,
                "register": reg, "ignore_handoff": ignore_handoff}

    def _run_admissions(self, picks, firsts: List[Tuple[int, Any]]
                        ) -> None:
        """Run the admission programs for already-seated picks (engine
        thread only — slot/cache state is loop-private)."""
        for slot, rid, req, handoff, plan in picks:
            if self.prefill_chunk:
                # seated, its pages granted; its chunks run in
                # _advance_prefills, and until the last one the slot
                # takes no part in a decode step
                self._jobs.append(_PrefillJob(
                    slot, rid, req,
                    np.asarray(req.prompt, np.int32).reshape(-1)))
                with self._lock:
                    self._prefilling[slot] = True
                    self._slot_len[slot] = 0
                continue
            with dtrace.use(req.ctx):
                if handoff is not None:
                    firsts.append((rid, self._inject_into(
                        slot, handoff, req, plan)))
                else:
                    firsts.append((rid, self._prefill_into(
                        slot, req, plan)))
            req._stamps.append(time.perf_counter())

    def _sampling_of(self, req: Request):
        """What a prefill program takes to sample ``req``'s first
        token: (rng key, temperature, top_k, top_p). An explicit resume
        chain is device-committed: a numpy key is a DIFFERENT jit-cache
        entry from the PRNGKey device array the normal path passes, so
        leaving it raw would recompile every prefill bucket once per
        crash re-dispatch."""
        key = (jax.random.PRNGKey(req.seed) if req.rng is None  # noqa: MXL301 — chain position 0 is PRNGKey(seed) by definition; the rng branch is a mid-chain resume key
               else jax.numpy.asarray(np.asarray(req.rng, np.uint32)))
        return (key, np.float32(req.temperature),
                np.int32(self.cfg.vocab_size if req.top_k is None
                         else req.top_k),
                np.float32(1.0 if req.top_p is None else req.top_p))

    def _chunk_fn(self, last: bool):
        C = self.prefill_chunk
        fn = self._prefills.get((C, last))
        if fn is None:
            prog = (self._family.prefill_slot_paged_last if last
                    else self._family.prefill_slot_paged_chunk)
            fn = telemetry.watch_jit(
                partial(prog, self.cfg, mesh=self.mesh),
                f"serve_prefill_{'last' if last else 'chunk'}_b{C}",
                f"{prog.__name__}_b{C}",
                donate_argnums=(7,) if last else (3,))
            self._prefills[(C, last)] = fn
        return fn

    def _advance_prefills(self, firsts: List[Tuple[int, Any]]) -> None:
        """Chunked prefill's share of an iteration (engine thread): the
        head prompt's next chunk, and further chunks only while the
        prompts waiting outnumber the requests running — a bank that is
        filling prefills; a bank that is running stalls by one chunk."""
        C = self.prefill_chunk
        while self._jobs:
            job = self._jobs[0]
            if self._slot_rid[job.slot] != job.rid:
                # cancelled and finalized while it waited: _process
                # freed the slot and its pages
                self._jobs.popleft()
                again = any(j.slot == job.slot for j in self._jobs)
                with self._lock:
                    self._prefilling[job.slot] = again
                continue
            req, left = job.req, job.prompt.size - job.done
            padded = np.zeros((1, C), np.int32)
            padded[0, :min(left, C)] = job.prompt[job.done:job.done + C]
            with dtrace.use(req.ctx), self._span_prefill(
                    bucket=C, role=self.role, prefix_len=job.done):
                if left > C:
                    self._stage = self._chunk_fn(False)(
                        self.params, padded, np.int32(job.done),
                        self._stage)
                    job.done += C
                else:
                    tok, self._kv, self._sv = self._chunk_fn(True)(
                        self.params, padded, np.int32(job.done),
                        np.int32(left), self._stage,
                        self._pt[job.slot].copy(), np.int32(job.slot),
                        self._kv, self._sv, *self._sampling_of(req))
                    self._jobs.popleft()
                    with self._lock:
                        self._prefilling[job.slot] = False
                        self._slot_len[job.slot] = job.prompt.size
                    firsts.append((job.rid, tok))
                    req._stamps.append(time.perf_counter())
            if len(self._jobs) <= int(
                    (self._active & ~self._prefilling).sum()):
                break

    # -- admission programs --------------------------------------------------
    def _prefill_fn(self, bucket: int):
        fn = self._prefills.get(bucket)
        if fn is None:
            fn = telemetry.watch_jit(
                partial(self._family.prefill_slot_paged, self.cfg,
                        mesh=self.mesh),
                f"serve_prefill_b{bucket}",
                f"prefill_slot_paged_b{bucket}", donate_argnums=(6,))
            self._prefills[bucket] = fn
        return fn

    def _run_prefill(self, slot: int, req: Request, suffix,
                     total_len: int, prefix_len: int):
        """One warm/cold prefill: the SUFFIX tokens (end-padded
        to their bucket) run at ``pos=prefix_len`` over the slot's
        gathered pages. The suffix bucket is what keys the program, so
        warm admissions hit SMALLER buckets than their full prompt
        would — the prefix-share TTFT win."""
        bucket = bucket_for(int(suffix.size), self.min_bucket,
                            self.max_len)
        fn = self._prefill_fn(bucket)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :suffix.size] = suffix
        with self._span_prefill(bucket=bucket, role=self.role,
                                prefix_len=prefix_len):
            tok, self._kv, self._sv = fn(
                self.params, padded, np.int32(total_len),
                np.int32(prefix_len), self._pt[slot].copy(),
                np.int32(slot), self._kv, self._sv,
                *self._sampling_of(req))
        with self._lock:
            self._slot_len[slot] = total_len
        return tok

    def _prefill_into(self, slot: int, req: Request, plan):
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        m = plan["prefix_len"]
        if plan["fork"] is not None:
            # CoW: the suffix writes into the shared boundary page —
            # give this slot a private copy first (the copy program
            # and the prefill order by data dependency on the pool)
            src, dst = plan["fork"]
            self._kv = self._copy_fn(self._kv, np.int32(src),
                                     np.int32(dst))
            with self._lock:
                self._cow_forks += 1
                # the copy is dispatched (ordered by data dependency
                # on the pool) — drop the planner's pin on the source
                self._pages.release([src])
            self._m["cow"].inc()
        tok = self._run_prefill(slot, req, prompt[m:],
                                int(prompt.size), m)
        reg = plan["register"]
        if reg is not None:
            if reg["copy"] is not None:
                # the entry's partial boundary page is a cache-owned
                # COPY of the slot's — decode writes past the prompt
                # must never leak into the registered prefix
                src, dst = reg["copy"]
                self._kv = self._copy_fn(self._kv, np.int32(src),
                                         np.int32(dst))
            with self._lock:
                self._prefix.insert(reg["tokens"], reg["n_tokens"],
                                    reg["pages"])
                if reg["copy"] is not None:
                    # insert() retains; drop the planner's temp hold
                    self._pages.release([reg["copy"][1]])
        return tok

    def _inject_block_len(self, h: KVHandoff) -> int:
        """The block length the inject program runs at. The
        page-granular wire trims handoff blocks to the page multiple
        covering ``true_len`` — an ARBITRARY multiple per prompt
        length — so injecting at the wire shape would compile up to
        max_len/page_size distinct programs. Pad back up to the
        power-of-two bucket (page-rounded) instead: inject compiles
        stay bounded by the bucket set, same as prefill."""
        blk = int(h.k.shape[2])
        b = bucket_for(blk, self.min_bucket, self.max_len)
        b = -(-b // self.page_size) * self.page_size
        return max(blk, b)

    def _inject_into(self, slot: int, h: KVHandoff,
                     req: Request, plan):
        """Admission of a handed-off prefill (disaggregated mode): one
        compiled inject program per block bucket writes the KV block's
        pages + the per-slot vectors; the first token was already
        sampled on the prefill worker and is returned as a HOST array
        (``_process`` reads firsts uniformly). When the request's
        prompt is LONGER than the handoff (journaled-page resume after
        a crash), the emitted suffix warm-prefills over the injected
        pages — one admission, no prefill-worker round trip."""
        if plan.get("ignore_handoff"):
            return self._prefill_into(slot, req, plan)
        bucket = self._inject_block_len(h)
        k, v = np.asarray(h.k), np.asarray(h.v)
        if bucket > k.shape[2]:
            # wire-trimmed block: zero-pad to the bucket (positions
            # past true_len are length-masked, so the fill is inert)
            pad = [(0, 0)] * k.ndim
            pad[2] = (0, bucket - k.shape[2])
            k, v = np.pad(k, pad), np.pad(v, pad)
        fn = self._injects.get(bucket)
        if fn is None:
            fn = telemetry.watch_jit(
                partial(self._family.inject_paged_kv, self.cfg,
                        mesh=self.mesh),
                f"serve_inject_b{bucket}", f"inject_paged_kv_b{bucket}",
                donate_argnums=(7,))
            self._injects[bucket] = fn
        with self._span_prefill(bucket=bucket, inject=True,
                                role=self.role):
            self._kv, self._sv = fn(
                k, v, np.int32(h.true_len), self._pt[slot].copy(),
                np.int32(slot), np.int32(h.token),
                np.asarray(h.rng, np.uint32), self._kv, self._sv)
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size > h.true_len:
            return self._run_prefill(
                slot, req, prompt[h.true_len:], int(prompt.size),
                int(h.true_len))
        with self._lock:
            self._slot_len[slot] = h.true_len
        return np.asarray([h.token], np.int32)

    def _seat(self, slot: int, rid: int, req: Request) -> None:
        self._active[slot] = True
        self._temps[slot] = req.temperature
        self._topks[slot] = (self.cfg.vocab_size if req.top_k is None
                             else req.top_k)
        self._topps[slot] = 1.0 if req.top_p is None else req.top_p
        self._slot_rid[slot] = rid
        if self.speculate_k:
            # drafting context: the prompt now, every emission later
            # (a journaled-resume prompt already carries the tokens
            # emitted before the crash — exactly the right context)
            self._hist[slot] = [
                int(t) for t in
                np.asarray(req.prompt, np.int32).reshape(-1)]

    # -- stepping ------------------------------------------------------------
    def _build_drafts(self) -> Optional[np.ndarray]:
        """Host drafting for one speculative step: up to
        ``speculate_k`` tokens per active slot from the pluggable
        drafter, clamped to ``max_new_tokens - emitted - 1`` so every
        accepted write stays inside the slot's granted pages (the
        admission plan covers prompt + max_new_tokens, and the last
        emitted token's KV is never written). Returns (S, k) int32
        with -1 marking no-draft, or None when NO slot drafted — the
        loop then runs the plain decode program (mixed stepping)."""
        K = self.speculate_k
        drafts = np.full((self.max_slots, K), -1, np.int32)
        any_d = False
        with self._lock:
            for s, rid in enumerate(self._slot_rid):
                if rid is None or not self._active[s]:
                    continue
                req = self._requests.get(rid)
                res = self._results.get(rid)
                if req is None or res is None \
                        or self._done.get(rid, True) \
                        or rid in self._cancelled:
                    continue
                hist = self._hist[s]
                # steady-state invariant: hist ends with the pending
                # token w0 (device length + 1 entries). A slot
                # admitted THIS step has its first token still
                # device-side — it drafts nothing this once
                if len(hist) <= int(self._slot_len[s]):
                    continue
                budget = min(K, int(req.max_new_tokens) - len(res) - 1)
                if budget < 1:
                    continue
                d = np.asarray(
                    self._drafter(np.asarray(hist, np.int32), budget),
                    np.int32).reshape(-1)[:budget]
                if d.size:
                    drafts[s, :d.size] = d
                    any_d = True
        return drafts if any_d else None

    def _dispatch(self, firsts) -> _Dispatch:
        # host DISPATCH time only — the program runs async; device time
        # belongs to the XLA trace (no sync in the decode loop, MXL004)
        drafts = self._build_drafts() if self.speculate_k else None
        emits = proposed = None
        with self._span_decode():
            if drafts is not None:
                # the k-verify step: one batched forward over each
                # slot's current token + drafts, accept-by-identity
                # down the same rng chain (decode_slots_spec)
                sampled, emits, self._kv, self._sv = self._spec_decode(
                    self.params, self._kv, self._sv, self._active,
                    self._pt, drafts, self._temps, self._topks,
                    self._topps)
                proposed = (drafts >= 0).sum(axis=1).astype(np.int64)
            else:
                # the page table rides as a small int32 operand —
                # table edits at admission never touch device state
                # or the jit cache key. A slot whose prompt is still
                # in chunks is seated but does not run: inactive, and
                # its row the scratch page's, like a free slot's
                # (a copy of the mask: the CPU backend reads a numpy
                # operand where it lies, while the program runs, and
                # _process edits _active under the next step; a program
                # that reads it in every layer would see it change)
                active, pt = self._active.copy(), self._pt
                if self._prefilling.any():
                    active = active & ~self._prefilling
                    pt = pt.copy()
                    pt[self._prefilling] = 0
                sampled, self._kv, self._sv = self._decode(
                    self.params, self._kv, self._sv, active,
                    pt, self._temps, self._topks, self._topps)
        self._m["steps"].inc()
        self._m["steps_plain" if drafts is None else "steps_verify"].inc()
        with self._lock:
            self.steps_run += 1
            if drafts is not None:
                self._spec_steps += 1
            slots = [(s, rid) for s, rid in enumerate(self._slot_rid)
                     if self._active[s] and rid is not None
                     and not self._prefilling[s]]
        # how far each slot's length advanced is the device's to say (a
        # speculative step's accepted run, a block's commit): _process
        # mirrors it at the readback
        return _Dispatch(sampled, slots, firsts, emits=emits,
                         proposed=proposed)

    def _emit(self, rid: int, token: int, now: float) -> None:
        self._results[rid].append(token)
        self._m["tokens"].inc()
        last = self._last_tok.get(rid)
        if last is None:
            # a request's first emission, wherever it comes from (a
            # prefill's first token, a block-diffusion slot's first
            # finished block)
            self._observe_ttft(rid, now)
        else:
            gap_ms = 1e3 * (now - last)
            self._lat.observe(gap_ms)
            self._m["latency"].observe(gap_ms)
        self._last_tok[rid] = now
        req = self._requests[rid]
        if req.on_token is not None:
            req.on_token(rid, token)
        if len(self._results[rid]) >= req.max_new_tokens:
            self._finalize(rid, "complete")

    def _observe_ttft(self, rid: int, now: float) -> None:
        """The request's first token is on the host at ``now`` (lock
        held): its wait goes into the three TTFT histograms in this
        one call."""
        req = self._requests.get(rid)
        if req is None or len(req._stamps) != 3:
            return
        submit, picked, dispatched = req._stamps
        req._stamps = []
        self._m["ttft_queue"].observe(1e3 * (picked - submit))
        self._m["ttft_admit"].observe(1e3 * (dispatched - picked))
        self._m["ttft_first_wait"].observe(1e3 * (now - dispatched))

    def _process(self, disp: _Dispatch) -> None:
        """One step's readback and emissions: the ONE path for a step
        that yields 0..W tokens a slot (``_Dispatch`` says what each
        kind of step hands over)."""
        # the device sync happens OUTSIDE the lock — a submitter must
        # never block behind a device readback. serve.readback is the
        # time the host waits for the device
        with self._span_readback():
            ran = bool(disp.slots)
            sampled = np.asarray(disp.sampled) if ran else None
            emits = (np.asarray(disp.emits)
                     if disp.emits is not None and ran else None)
            firsts = [(rid, np.asarray(dev).reshape(-1))
                      for rid, dev in disp.firsts]
        now = time.perf_counter()
        if sampled is not None:
            # W tokens a slot, the family's step counts behind them
            sampled = sampled.reshape(-1)
            n = len(self._step_counts)
            sampled, counts = np.split(sampled, [sampled.size - n])
            if self._block_step:
                sampled, emits = np.split(sampled, 2)
                emits = emits.reshape(self.max_slots, -1) > 0
            sampled = sampled.reshape(self.max_slots, -1)
            for (series, per), value in zip(self._step_counts, counts):
                if per:
                    series.observe(float(value) / per)
                else:
                    series.inc(int(value))
        with self._span_emit(), self._lock:
            rid2slot = ({rid: s for s, rid in
                         enumerate(self._slot_rid) if rid is not None}
                        if self.speculate_k else {})
            for rid, toks in firsts:
                if rid in self._cancelled:
                    continue
                for tok in toks:
                    self._emit(rid, int(tok), now)
                    s = rid2slot.get(rid)
                    if s is not None:
                        self._hist[s].append(int(tok))
            for slot, rid in disp.slots:
                toks = sampled[slot]
                if emits is not None:
                    toks = toks[emits[slot]]
                if self._slot_rid[slot] == rid:
                    # the device advanced this slot (a finished
                    # request's slot is freed below, and may hold its
                    # successor by now: its over-advance was inert and
                    # the successor's prefill reseeded the length)
                    self._slot_len[slot] += len(toks)
                if disp.proposed is not None:
                    # speculative step: 1 + the accepted run
                    n, prop = len(toks), int(disp.proposed[slot])
                    self._spec_proposed += prop
                    self._spec_accepted += n - 1
                    if prop:
                        self._m["spec_proposed"].inc(prop)
                        self._m["spec_accepted"].inc(n - 1)
                    self._m["spec_len"].observe(n)
                for tok in toks:
                    # the emission loop stops at max_new_tokens/cancel
                    # (a pruned rid — non-retained, finalized — reads
                    # as done: never emit for it); what the device ran
                    # past it is inert
                    if self._done.get(rid, True) \
                            or rid in self._cancelled:
                        break
                    self._emit(rid, int(tok), now)
                    if self.speculate_k:
                        self._hist[slot].append(int(tok))
            for slot, rid in enumerate(self._slot_rid):
                if rid is None:
                    continue
                reason = self._cancelled.get(rid)
                if reason is not None:
                    self._finalize(rid, reason)
                if self._done.get(rid, True):
                    self._active[slot] = False   # recycle at the next
                    self._slot_rid[slot] = None  # step boundary
                    # release the slot's page hold; prefix-cache
                    # entries keep their own refs, so shared pages
                    # survive the request that seeded them
                    row = self._pt[slot]
                    held = [int(p) for p in row if p]
                    if held:
                        self._pages.release(held)
                    row[:] = 0
            self._m["slots"].set(int(self._active.sum()))
            self._m["pages_free"].set(self._pages.free_pages)
            self._m["pages_shared"].set(self._pages.shared_pages)
            live = self._live_bytes()
            self._m["kv_live"].set(live)
            self._m["kv_occ"].set(live / self._kv_reserved
                                  if self._kv_reserved else 0.0)

    # -- the serving loop ----------------------------------------------------
    def _loop_iter(self, prev: Optional[_Dispatch]
                   ) -> Optional[_Dispatch]:
        """One engine step: sweep cancels/deadlines, admit, dispatch,
        and (overlap permitting) process the PREVIOUS step's tokens
        under this step's device time. Shared by :meth:`run` (batch
        drain) and :meth:`run_forever` (the gateway's replica loop)."""
        firsts: List[Tuple[int, Any]] = []
        with self._span_sweep(), self._lock:
            self._sweep_cancelled()
            picks = self._pick_admissions()
        with self._span_admit():
            self._run_admissions(picks, firsts)
            if self._jobs:
                self._advance_prefills(firsts)
        # any admission leaves its slot active (a chunked one when its
        # last chunk ran), so firsts are always carried by a dispatch
        out = (self._dispatch(firsts)
               if (self._active & ~self._prefilling).any() else None)
        if not self.overlap and out is not None:
            self._process(out)
            out = None
        if prev is not None:
            self._process(prev)
        with self._lock:
            self._step_idx += 1
        return out

    def run(self) -> Dict[int, np.ndarray]:
        """Drain the queue: admit → dispatch → (overlapped) process,
        until every submitted request has completed. Returns
        {rid: generated tokens} (prompts not included, matching the
        ``generate`` continuation; a cancelled request's entry holds
        whatever tokens it produced before its cancellation)."""
        prev: Optional[_Dispatch] = None
        while True:
            with self._lock:
                if not (self._queue or self._active.any()
                        or prev is not None):
                    break
            prev = self._loop_iter(prev)
            with self._lock:
                if (prev is None and not self._active.any()
                        and self._queue):
                    # idle until the next scheduled arrival
                    self._step_idx = max(self._step_idx,
                                         self._queue[0][0])
        with self._lock:
            return {rid: np.asarray(toks, np.int32)
                    for rid, toks in self._results.items()}

    def run_forever(self, stop: threading.Event,
                    idle_wait: float = 0.02) -> None:
        """The replica loop: serve submissions as they arrive until
        ``stop`` is set, then DRAIN — in-flight and queued requests
        finish (or hit their deadlines) before the loop exits, so a
        scale-down never drops accepted work. Idle waits block on the
        submit/cancel condition, bounded by ``idle_wait`` so a stop
        with no traffic is noticed promptly."""
        prev: Optional[_Dispatch] = None
        while True:
            with self._cv:
                work = (bool(self._queue) or self._active.any()
                        or prev is not None)
                if not work:
                    if stop.is_set():
                        break
                    self._cv.wait(idle_wait)
                    continue
                if (prev is None and not self._active.any()
                        and self._queue
                        and self._queue[0][0] > self._step_idx):
                    # future-only arrivals (seeded streams): jump the
                    # step clock instead of spinning
                    self._step_idx = self._queue[0][0]
            prev = self._loop_iter(prev)

    def wake(self) -> None:
        """Nudge an idle :meth:`run_forever` (the gateway calls this
        right after setting the stop event)."""
        with self._cv:
            self._cv.notify_all()

    def load(self) -> Dict[str, int]:
        """Routing snapshot: queued (submitted, not yet seated),
        active slots, and the bank size — what the gateway's
        least-loaded router and autoscaler read."""
        with self._lock:
            queued = sum(1 for _, rid, _r in self._queue
                         if rid not in self._ended)
            return {"queued": queued,
                    "active": int(self._active.sum()),
                    "slots": self.max_slots}

    # -- introspection -------------------------------------------------------
    @property
    def compile_count(self) -> int:
        """Compiled programs this engine has built: one per admission
        bucket (prefill or, in disaggregated mode, inject), the
        single decode program and the page copy. The churn test gates
        this at ``buckets + 2`` — requests entering/leaving must never
        retrace."""
        # deliberately NO fallback: if jax moves the private
        # _cache_size API this raises loudly — a silent
        # len(fns) stand-in would make the no-retrace gate
        # vacuously true exactly when a retrace bug could hide
        # the CoW fork/registration copy is ONE program (src/dst are
        # traced scalars)
        fns = ([self._decode, self._copy_fn]
               + list(self._prefills.values())
               + list(self._injects.values()))
        if self._spec_decode is not None:
            # speculative mode adds exactly ONE watched program (the
            # k-verify step) — the spec bound is buckets + 3
            fns.append(self._spec_decode)
        return int(sum(f._cache_size() for f in fns))

    @property
    def n_buckets(self) -> int:
        """Distinct admission buckets compiled so far — prefill
        programs plus (disaggregated mode) inject programs; the
        compile bound is ``n_buckets + 2`` either way."""
        return len(self._prefills) + len(self._injects)

    def _live_bytes(self) -> int:
        """Bytes of the state that live requests cover (lock held):
        their tokens' keys and values and their slots' fixed blocks."""
        return (int(self._slot_len[self._active].sum())
                * self._kv_tok_bytes
                + int(self._active.sum()) * self._slot_state_bytes)

    def kv_cache_stats(self) -> Dict[str, Any]:
        """KV occupancy: bytes the donated state (page pool, and a
        family's fixed per-slot state) RESERVES vs bytes live sequence
        prefixes actually COVER, with the pool's page counts, prefix
        cache and speculation tallies — surfaced in the gateway
        ``/state`` block. ``"paged"`` is always True (readers outside
        the repo may hold the key); ``"decode_attention"`` is what the
        decode program's attention was built on, ``"pages"`` (the
        kernel over live pages), ``"gathered"``, or a retention step's
        ``"state_kernel"`` / ``"state"`` (no keys or values: the Pallas
        kernel over the bank, or the ``jnp`` form), as the family's
        ``decode_attention_path`` gave it; ``"sampler"`` is how the
        decode program's sampler finds its two thresholds,
        ``"search_kernel"`` (the Pallas kernel) or ``"search"`` (the
        ``jnp`` form), as ``ops.threshold.thresholds_path`` gave it.
        Host arithmetic only (the mirrored per-slot lengths; reading the
        device ``lengths`` vector here would put a sync next to the
        decode loop — MXL004)."""
        with self._lock:
            active = int(self._active.sum())
            live = self._live_bytes()
            out = {"slots": self.max_slots, "active": active,
                   "reserved_bytes": self._kv_reserved,
                   "state_bytes_per_slot": self._slot_state_bytes,
                   "paged": True,
                   "decode_attention": self._attention["plain"],
                   "sampler": self._sampler,
                   "page_size": self.page_size,
                   "pages_total": self.n_pages - 1,
                   "pages_free": self._pages.free_pages,
                   "pages_used": self._pages.used_pages,
                   "pages_shared": self._pages.shared_pages,
                   "cow_forks": self._cow_forks,
                   "prefix_hits": self._prefix_hits,
                   "prefix_misses": self._prefix_misses,
                   "prefix_entries": (len(self._prefix)
                                      if self._prefix is not None
                                      else 0),
                   "top_prefixes": (self._prefix.top()
                                    if self._prefix is not None
                                    else [])}
            if self.speculate_k:
                prop = self._spec_proposed
                out.update({
                    "speculate_k": self.speculate_k,
                    "spec_proposed": prop,
                    "spec_accepted": self._spec_accepted,
                    "spec_accept_rate": (
                        self._spec_accepted / prop if prop else 0.0),
                    "spec_steps": self._spec_steps,
                })
        out["live_bytes"] = live
        out["occupancy"] = (live / self._kv_reserved
                            if self._kv_reserved else 0.0)
        return out

    def device_state(self) -> Dict[str, Any]:
        """The bank as the last dispatched program left it, by the
        family's names (``init_paged_cache``'s: the donated state and the
        per-slot vectors), for a check of what a served slot holds. The
        next program donates these arrays: read them while the engine is
        idle, and copy what is kept before more work is submitted (the
        loop thread alone writes them, under no lock)."""
        return {**self._kv, **self._sv}

    def latency_stats(self) -> Dict[str, float]:
        """Per-token latency: p50/p99 over the gaps between a
        request's consecutive tokens (ms), from this engine's private
        fixed-bucket histogram (bounded memory — the unbounded
        per-token log it replaces grew with every request; the same
        gaps also feed the process-wide ``serve_token_latency_ms``)."""
        n = self._lat.count
        if n == 0:
            return {"p50_token_ms": 0.0, "p99_token_ms": 0.0,
                    "n_gaps": 0}
        return {"p50_token_ms": float(self._lat.percentile(50)),
                "p99_token_ms": float(self._lat.percentile(99)),
                "n_gaps": n}

    def reset_stats(self) -> None:
        """Zero the per-engine latency histogram + step counter (the
        bench warmup boundary). Speculative accept counters reset with
        it so a bench's accept rate excludes warmup traffic."""
        with self._lock:      # _emit observes/updates these under _lock
            self._lat.reset()
            self._last_tok.clear()
            self.steps_run = 0
            self._spec_proposed = 0
            self._spec_accepted = 0
            self._spec_steps = 0
