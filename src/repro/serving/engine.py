"""Continuous-batching inference engine.

Slot-based scheduler in the vLLM/Orca style, adapted to JAX static shapes:
a fixed decode batch of ``max_slots`` sequences steps together through a
jitted ``decode_step``; free slots admit queued requests via ``prefill``
whose KV is written into the slot.  Everything is asyncio — PopPy's burst
of parallel `@unordered` LLM calls lands here and shares decode batches
(the batching co-design of DESIGN.md §3.2).

Prompt ingestion is cheap and non-blocking (DESIGN.md §3.2):

* **Radix prefix cache** (`prefix_cache.py`) — prefilled KV is stored
  along a token trie; a request reuses its longest cached prefix and only
  prefills the suffix from the cached boundary.  A burst of N fan-out
  requests sharing a long context prefills it once
  (``LocalEngineBackend.generate_batch`` warms it explicitly).
* **Bucketed prefill** — prompts pad to a small set of length buckets
  (powers of two up to ``max_len``), so steady-state traffic hits a
  handful of compiled shapes instead of one compilation per prompt
  length; ``prefill_compilations`` counts distinct compiled shapes and
  ``prefill_shape_bound`` is the bucketing-guaranteed ceiling (the CI
  perf gate watches the ratio).
* **Chunked prefill** — long prompts prefill in ``prefill_chunk``-token
  chunks scheduled between decode steps (iteration-level scheduling), so
  one long admit never freezes the live decode batch.

These all ride on the prefix-aware ``Model.prefill`` and require
positionally sliceable KV (``Model.prefix_seq_axes``); recurrent/hybrid/
enc_dec/int8-KV models fall back to the exact-length one-shot prefill.

Straggler mitigation: per-request deadline + hedged retry at the client
(`LocalEngineBackend`); a cancelled request (hedge loser, abandoned
client) is dropped from the queue or has its slot freed at the next
step, so duplicates never decode to ``max_new_tokens`` in the dark.
"""

from __future__ import annotations

import asyncio
import contextlib
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.spans import DETACHED, current_tracer, maybe_span
from repro.sharding.rules import (
    cache_pspecs,
    make_serving_rules,
    named,
    params_pspecs,
    use_rules,
)
from repro.serving.prefix_cache import (
    PagedPrefixCache,
    PrefixCache,
    tree_concat,
    tree_nbytes,
    tree_pad_to,
    tree_slice,
)
from repro.serving.sampler import sample_tokens, sample_tokens_batched

#: What ``ServingEngine._span`` returns in a pass that is not traced.
_UNTRACED = contextlib.nullcontext()


@dataclass
class Request:
    prompt_tokens: list
    max_new_tokens: int
    temperature: float = 0.0
    done: asyncio.Future | None = None
    out_tokens: list = field(default_factory=list)
    slot: int = -1
    # host times, ``time.perf_counter()`` seconds (the tracer's clock)
    submitted_at: float = 0.0
    started_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0
    # observability: the client-side request span (and its tracer) — the
    # scheduler loop parents its per-request work (admission, prefill
    # chunks) under it explicitly, since the loop task doesn't run in the
    # submitting client's context
    trz: object = None
    span: object = None

    @property
    def abandoned(self) -> bool:
        """The client is gone (cancelled hedge duplicate, dropped call):
        nobody will consume the result, so the engine must not spend
        decode steps on it."""
        return self.done is not None and self.done.done()


@dataclass
class _PrefillTask:
    """A prompt being prefilled, possibly across several chunks.  ``req``
    is None for cache-warm tasks (shared-prefix admission), which compute
    and insert KV without occupying a decode slot."""

    tokens: tuple
    req: Request | None = None
    slot: int = -1
    done: asyncio.Future | None = None     # warm-task completion
    started: bool = False
    matched: int = 0                       # tokens served by the radix cache
    handle: object = None                  # prefix-cache pin
    pinned_in: object = None               # the PrefixCache instance pinned
    acc: object = None                     # KV pytree covering tokens[:covered]
    covered: int = 0
    last_logits: object = None
    trz: object = None                     # tracer for warm tasks
    span: object = None                    # warm-task span (open until done)
    # paged-KV ownership (kv_layout == "paged")
    page_row: list | None = None           # matched + fresh page ids, in order
    fresh_ids: list | None = None          # pages this task allocated itself


class PageAllocator:
    """Free-list allocator over the KV page pool (DESIGN.md §3.3).

    Page 0 is reserved as *scratch*: retired slots' page tables point at
    it, so their (masked) per-step decode writes land somewhere harmless
    instead of corrupting live pages.  Every other page is handed out
    with refcount 1; the radix trie and admitted slots take additional
    refs on shared prefix pages, and a page returns to the free list only
    when its last owner drops it — there is no copying anywhere in the
    ownership protocol.

    Metrics (PR 6 registry): ``serving_pages_free`` / ``serving_pages_pinned``
    gauges and ``serving_page_fault`` / ``serving_page_evict`` counters.
    """

    def __init__(self, num_pages: int, page_size: int, *, metrics=None):
        self.num_pages = num_pages
        self.page_size = page_size
        self._free = list(range(num_pages, 0, -1))  # pop() yields 1, 2, ...
        self._refs = np.zeros(num_pages + 1, np.int64)
        self.page_faults = 0
        self.page_evicts = 0
        self._c_fault = metrics.counter("serving_page_fault") \
            if metrics else None
        self._c_evict = metrics.counter("serving_page_evict") \
            if metrics else None
        self._g_free = metrics.gauge("serving_pages_free") \
            if metrics else None
        self._g_pinned = metrics.gauge("serving_pages_pinned") \
            if metrics else None
        if self._g_free is not None:
            self._g_free.set(num_pages)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list | None:
        """n pages at refcount 1, or None (all-or-nothing: a partial grant
        would deadlock admission)."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._refs[i] = 1
        self._note_free()
        return ids

    def incref(self, ids) -> None:
        for i in ids:
            assert i != 0 and self._refs[i] > 0, f"incref of dead page {i}"
            self._refs[i] += 1

    def decref(self, ids) -> int:
        """Drop one ref per id; pages reaching 0 return to the free list.
        Returns how many were freed."""
        freed = 0
        for i in ids:
            self._refs[i] -= 1
            assert self._refs[i] >= 0, f"double free of page {i}"
            if self._refs[i] == 0:
                self._free.append(i)
                freed += 1
        if freed:
            self._note_free()
        return freed

    def refcount(self, i: int) -> int:
        return int(self._refs[i])

    def note_fault(self) -> None:
        """Admission found too few free pages and must reclaim/stall."""
        self.page_faults += 1
        if self._c_fault is not None:
            self._c_fault.inc()

    def note_evict(self, n: int) -> None:
        self.page_evicts += n
        if self._c_evict is not None:
            self._c_evict.inc(n)

    def set_pinned(self, n: int) -> None:
        if self._g_pinned is not None:
            self._g_pinned.set(n)

    def _note_free(self) -> None:
        if self._g_free is not None:
            self._g_free.set(len(self._free))


def default_buckets(max_len: int, lo: int = 16) -> tuple:
    """Powers of two from ``lo`` up to (and always including) max_len."""
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


class ServingEngine:
    """Continuous batching over a repro.models Model on a (usually 1-device)
    mesh.  Designed so the same scheduler drives the 256-chip production
    mesh — the jitted steps are the ones the dry-run lowers.

    Knobs (see README §serving): ``prefix_cache_budget`` (bytes of radix
    KV to retain; 0/None disables), ``prefill_chunk`` (tokens per prefill
    chunk interleaved with decode; None = whole prompt in one chunk), and
    ``prefill_buckets`` (pad-to lengths for the jitted prefill; default
    powers of two up to ``max_len``).

    Tensor parallelism: pass ``mesh`` (a ``("data","model")`` mesh from
    ``launch.mesh.make_serving_mesh``) and the engine spans its devices —
    params and the KV pool are placed under the serving sharding rules
    (``sharding.rules.make_serving_rules``: heads/pool over the ``model``
    axis, page tables replicated) and every jitted step traces under them,
    so the models' ``shard_hint``s bind activations to the mesh.  The
    scheduler is unchanged; tokens are bit-identical to the single-device
    engine (same program, GSPMD-partitioned).  ``name`` labels this
    engine's observability tracks (``<name>:decode`` …) so fleet replicas
    stay distinguishable in one trace; empty keeps the bare track names."""

    def __init__(self, model, params, *, max_slots=8, max_len=256,
                 eos_token=None, step_sleep=0.0,
                 prefix_cache_budget=64 * 1024 * 1024,
                 prefill_chunk=None, prefill_buckets=None,
                 idle_quiesce_s=1.0, page_size=16, num_pages=None,
                 kv_layout=None, metrics=None, mesh=None, name=""):
        self.model = model
        self.cfg = model.cfg
        self.name = name
        self.mesh = mesh
        self._rules = make_serving_rules(mesh, model.cfg) \
            if mesh is not None else None
        if self._rules is not None:
            params = jax.device_put(
                params, named(self._rules,
                              params_pspecs(self._rules, model)))
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.eos_token = eos_token
        self.step_sleep = step_sleep
        self.idle_quiesce_s = idle_quiesce_s
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.queue: asyncio.Queue[Request] = asyncio.Queue()
        self.active: dict[int, Request] = {}
        self.free_slots = list(range(max_slots))
        self._pending: list[_PrefillTask] = []
        self._warm_waiting: list[_PrefillTask] = []
        self._wake: asyncio.Event | None = None
        self._wake_loop = None
        self._task = None
        self._stop = False
        self.steps = 0
        self.decode_tokens = 0
        # decode-batch occupancy: running max and sum over steps (the
        # registry, when one is given, also keeps a histogram)
        self.max_occupancy = 0
        self.occupancy_sum = 0
        self._h_occupancy = metrics.histogram("serving_batch_occupancy") \
            if metrics is not None else None
        # blocking device->host reads made by the loop (``_host``)
        self.host_syncs = 0
        self._c_syncs = metrics.counter("serving_host_syncs") \
            if metrics is not None else None
        # tracers of the requests and warm tasks the engine holds: the
        # loop task's own context predates any ``tracing()`` block, so a
        # pass records its spans on the newest of these
        self._tracers: list = []
        self._pass: _Pass | None = None     # the current traced pass
        self.prefill_shapes: set = set()
        # (prefix tokens, padded length) -> padded prefix KV.  A burst of
        # fan-out requests shares one matched prefix; without this every
        # request re-pads the same multi-MB pytree.  KV is a deterministic
        # function of the tokens, so entries are never stale — the cap
        # only bounds memory.
        self._pad_memo: dict = {}
        self._pad_memo_cap = 4
        self.prefill_chunks = 0
        self.prefill_tokens_computed = 0
        self.prefill_tokens_reused = 0
        # KV copied into the decode cache at admission.  The paged engine
        # must keep this at 0 for shared prefixes: a cache hit appends
        # page *references* (fig14 asserts it); the contiguous engine
        # splices a copy per admit.
        self.kv_admit_copies = 0
        self.admit_stalls = 0

        # prefix-aware prefill machinery: only for models whose cache is
        # positionally sliceable; others keep the exact-length path
        self._seq_axes = model.prefix_seq_axes()
        self._paged = self._seq_axes is not None
        if kv_layout not in (None, "paged", "contiguous"):
            raise ValueError(f"kv_layout must be 'paged' or 'contiguous', "
                             f"got {kv_layout!r}")
        # block-paged KV is the default wherever it is sound; models with
        # non-sliceable state (recurrent/hybrid/enc_dec/int8/windowed)
        # silently keep the contiguous slab
        self.kv_layout = "contiguous" if not self._paged \
            else (kv_layout or "paged")
        self.paged_kv = self.kv_layout == "paged"

        # each slot's position and current token: the host holds them
        # (a slot's position is a fact of its request) and uploads them to
        # the device arrays the decode step reads, so the loop never reads
        # them back; ``_slots_dirty`` marks an upload owed before a step
        self._pos_host = np.zeros((max_slots,), np.int32)
        self._cur_host = np.zeros((max_slots, 1), np.int32)
        self._slots_dirty = False
        self.positions = jnp.zeros((max_slots,), jnp.int32)
        self.cur_tokens = jnp.zeros((max_slots, 1), jnp.int32)
        self.live = np.zeros((max_slots,), bool)
        self._rng = jax.random.PRNGKey(0)
        self._sample_all = jax.jit(sample_tokens_batched)

        if self._paged:
            if self.paged_kv:
                if page_size < 1 or max_len % page_size:
                    raise ValueError(
                        f"max_len {max_len} must be a positive multiple of "
                        f"page_size {page_size}")
                self._buckets = tuple(sorted(prefill_buckets)) \
                    if prefill_buckets \
                    else default_buckets(max_len, lo=max(16, page_size))
                bad = [b for b in self._buckets if b % page_size]
                if bad:
                    raise ValueError(
                        f"prefill buckets {bad} are not multiples of "
                        f"page_size {page_size} (finalize scatters whole "
                        f"pages)")
            else:
                self._buckets = tuple(sorted(prefill_buckets)) \
                    if prefill_buckets else default_buckets(max_len)
            self._empty_prefix = tree_slice(
                model.init_cache(1, 1), self._seq_axes, 0, 0)

            def _px_fn(p, toks, pfx, plen, lidx):
                logits, cache = model.prefill(
                    p, {"tokens": toks}, capacity=toks.shape[1],
                    prefix=pfx, prefix_len=plen, last_index=lidx)
                return logits, self._pin_cache(cache, "contiguous")

            self._prefill_px = self._jit_sharded(_px_fn)
        else:
            self._buckets = ()
        self.prefill_chunk = prefill_chunk if self._paged else None

        def _exact_fn(p, b):
            logits, cache = model.prefill(p, b, capacity=max_len)
            return logits, self._pin_cache(cache, "contiguous")

        self._prefill_exact = self._jit_sharded(_exact_fn)

        if self.paged_kv:
            self._init_paged(page_size, num_pages, prefix_cache_budget)
        else:
            self.page_size = None
            self.num_pages = 0
            self.allocator = None
            self._wait_pages: list[Request] = []
            self.page_op_shapes: set = set()
            self.cache = self._new_cache(
                lambda: model.init_cache(max_slots, max_len), "contiguous")

            def _decode_fn(p, cache, toks, pos):
                logits, cache = model.decode_step(p, cache, toks, pos)
                return logits, self._pin_cache(cache, "contiguous")

            self._decode = self._jit_sharded(_decode_fn,
                                             donate_argnums=(1,))
            self.prefix_cache = (
                PrefixCache(self._seq_axes, prefix_cache_budget)
                if (self._paged and prefix_cache_budget) else None)
            if self._paged:
                def _splice_fn(cache, new, slot):
                    # donated in-place slot write: without it every
                    # admission copies the whole decode cache
                    # (max_slots · max_len KV)
                    def write(ax, cur, seg):
                        start = [0] * cur.ndim
                        start[ax - 1] = slot  # batch axis precedes seq
                        return jax.lax.dynamic_update_slice(
                            cur, seg.astype(cur.dtype), tuple(start))
                    out = jax.tree.map(write, self._seq_axes, cache, new)
                    return self._pin_cache(out, "contiguous")

                self._splice = self._jit_sharded(_splice_fn,
                                                 donate_argnums=(0,))

    def _init_paged(self, page_size, num_pages, prefix_cache_budget):
        """Block-paged KV state: a page pool shared by all slots + the
        radix trie, per-slot page tables, and the jitted page ops
        (gather for prefill reuse, scatter-fill at finalize, paged decode
        step).  Page 0 is allocator scratch — retired slots and padding
        point at it."""
        self.page_size = page_size
        self.pages_per_slot = self.max_len // page_size
        self.num_pages = int(num_pages) if num_pages \
            else self.max_slots * self.pages_per_slot
        if self.num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {self.num_pages}")
        # a pool smaller than one full sequence is fine (short-request
        # traffic): generate() rejects any request whose eager page need
        # exceeds the pool, so admission can never stall forever
        self.allocator = PageAllocator(self.num_pages, page_size,
                                       metrics=self.metrics)
        # pool leaf shape: [n_groups, num_pages+1, page_size, KVH, hd]
        self.kv_pages = self._new_cache(
            lambda: self.model.init_paged_cache(self.num_pages + 1,
                                                page_size), "paged")
        self._page_table = np.zeros((self.max_slots, self.pages_per_slot),
                                    np.int32)
        self._table_dev = jnp.asarray(self._page_table)
        self._table_dirty = False
        self._slot_pages: dict[int, list] = {}
        self._wait_pages: list[Request] = []   # admission backpressure
        self.page_op_shapes: set = set()
        self.cache = None

        def _decode_paged_fn(p, pools, toks, pos, table):
            logits, pools = self.model.decode_step_paged(p, pools, toks,
                                                         pos, table)
            return logits, self._pin_cache(pools, "paged")

        self._decode_paged = self._jit_sharded(_decode_paged_fn,
                                               donate_argnums=(1,))
        self._page_gather = self._jit_sharded(
            lambda pools, ids: self._pin_cache(
                self._gather_fn(pools, ids), "contiguous"))
        self._page_fill = self._jit_sharded(
            lambda pools, seg, ids: self._pin_cache(
                self._fill_fn(pools, seg, ids), "paged"),
            donate_argnums=(0,))
        if prefix_cache_budget:
            page_bytes = tree_nbytes(self.kv_pages) // (self.num_pages + 1)
            budget_pages = int(prefix_cache_budget // max(1, page_bytes))
            self.prefix_cache = (
                PagedPrefixCache(self.allocator, budget_pages)
                if budget_pages > 0 else None)
        else:
            self.prefix_cache = None

    def _gather_fn(self, pools, ids):
        """Gather pages ``ids`` into a contiguous [*, 1, n·ps, ...] prefix
        view for prefix-aware prefill.  A transient *read* for attention —
        the slot's KV stays in the shared pages (no admit copy)."""
        def g(ax, pool):
            t = jnp.take(pool, ids, axis=ax - 1)
            shp = list(t.shape)
            return t.reshape(shp[:ax - 1] + [1, shp[ax - 1] * shp[ax]]
                             + shp[ax + 1:])
        return jax.tree.map(g, self._seq_axes, pools)

    def _fill_fn(self, pools, seg, ids):
        """Scatter freshly prefilled KV ``seg`` ([*, 1, n·ps, ...]) into
        pool pages ``ids`` (donated: in-place on the pool).  Padding ids
        are 0 — the scratch page absorbs them."""
        n = ids.shape[0]

        def w(ax, pool, s):
            shp = list(s.shape)
            pages = s.reshape(shp[:ax - 1] + [n, self.page_size]
                              + shp[ax + 1:])
            idx = (slice(None),) * (ax - 1) + (ids,)
            return pool.at[idx].set(pages.astype(pool.dtype))
        return jax.tree.map(w, self._seq_axes, pools, seg)

    # -- tensor-parallel placement (no-ops without a mesh) ---------------------

    def _jit_sharded(self, fn, **jit_kwargs):
        """``jax.jit(fn)``, tracing under the engine's serving rules so the
        models' ``shard_hint``s resolve against the mesh.  Rules bind at
        trace time via the ``sharding.rules`` contextvar; compiled
        executables keep them baked in."""
        if self._rules is None:
            return jax.jit(fn, **jit_kwargs)
        rules = self._rules

        def traced(*args):
            with use_rules(rules):
                return fn(*args)

        return jax.jit(traced, **jit_kwargs)

    def _pin_cache(self, tree, layout: str):
        """Constrain a cache/pool pytree (inside jit) to its canonical
        layout, so donated KV buffers keep a stable sharding across steps
        — without the pin, GSPMD is free to re-layout each compiled shape
        and donation degenerates into resharding copies."""
        if self._rules is None:
            return tree
        shardings = named(self._rules,
                          cache_pspecs(self._rules, tree, layout=layout))
        return jax.tree.map(jax.lax.with_sharding_constraint,
                            tree, shardings)

    def _new_cache(self, make, layout: str):
        """A freshly initialized cache/pool, ``make()``, built by one
        compiled program.  Run eagerly, each stacked leaf would pass
        through a second full-size copy (broadcast, then copy).  Under a
        mesh it is made in place, on the mesh's devices in its canonical
        layout: made on the default device and then moved, it would take
        that device's memory for a while, and a pool sized for TP may not
        fit there."""
        if self._rules is None:
            return jax.jit(make)()
        shardings = named(self._rules, cache_pspecs(
            self._rules, jax.eval_shape(make), layout=layout))
        return jax.jit(make, out_shardings=shardings)()

    def _tr(self, track: str) -> str:
        """Observability track name, replica-prefixed when the engine is
        named (fleet replicas share one trace)."""
        return f"{self.name}:{track}" if self.name else track

    # -- client API -----------------------------------------------------------

    def prefix_probe(self, tokens) -> int:
        """Longest radix-cached prefix of ``tokens`` (read-only; 0 when
        prefix caching is disabled).  The per-replica digest behind
        dispatch's prefix-affinity routing."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.probe(tokens)

    async def generate(self, prompt_tokens, *, max_new_tokens=32,
                       temperature=0.0) -> list:
        prompt_tokens = list(prompt_tokens)
        if len(prompt_tokens) >= self.max_len:
            # reject at submission: admitting it would overflow the slot
            # cache (and mint unbounded prefill shapes) — fail the one
            # request, never the scheduler
            raise ValueError(
                f"prompt of {len(prompt_tokens)} tokens needs at least "
                f"one decode position; engine max_len is {self.max_len}")
        if self.paged_kv:
            # page-granular admission check: pages are allocated eagerly
            # for prompt + max_new at admit (no mid-decode OOM), so a
            # request needing more pages than the whole pool would stall
            # admission forever — reject it at submission instead
            total = min(len(prompt_tokens) + max_new_tokens, self.max_len)
            need = -(-total // self.page_size)
            if need > self.num_pages:
                raise ValueError(
                    f"request needs {need} KV pages ({len(prompt_tokens)} "
                    f"prompt + {max_new_tokens} new tokens at page_size "
                    f"{self.page_size}) but the pool holds only "
                    f"{self.num_pages} pages even with everything "
                    f"evicted — it could never be admitted")
        req = Request(prompt_tokens, max_new_tokens, temperature,
                      done=asyncio.get_running_loop().create_future(),
                      submitted_at=time.perf_counter())
        trz = current_tracer()
        if trz is None:
            await self.queue.put(req)
            self._wake_event().set()
            self.ensure_running()
            return await req.done
        # the request span covers the whole lifecycle (queue wait →
        # admission → prefill chunks → shared decode steps → finish) from
        # the client's side; scheduler-side spans attach to it by parent
        req.trz = trz
        self._tracers.append(trz)
        try:
            with trz.span("request", cat="serving.request",
                          n_prompt=len(prompt_tokens),
                          max_new=max_new_tokens) as sp:
                req.span = sp
                await self.queue.put(req)
                self._wake_event().set()
                self.ensure_running()
                out = await req.done
                sp.attrs["n_out"] = len(out)
                sp.attrs["first_token_t"] = req.first_token_at
                return out
        finally:
            self._tracers.remove(trz)

    def prompt_logits(self, tokens):
        """Logits [1, V] for the token after ``tokens``, through the
        engine's own jitted prefill with nothing cached: the distribution
        a fresh request's first token is drawn from.  For checking the
        serving path against a direct ``Model.prefill``."""
        toks = list(tokens)
        if self._paged:
            logits, _ = self._run_prefill(toks, None, 0)
        else:
            logits, _ = self._prefill_exact(
                self.params, {"tokens": jnp.asarray([toks], jnp.int32)})
        return logits

    def _wake_event(self) -> asyncio.Event:
        # asyncio primitives bind to the loop they are first used on; the
        # engine outlives benchmark/test loops, so the event is per-loop
        loop = asyncio.get_running_loop()
        if self._wake is None or self._wake_loop is not loop:
            self._wake = asyncio.Event()
            self._wake_loop = loop
        return self._wake

    async def warm_prefix(self, tokens) -> dict | None:
        """Ensure ``tokens`` (a shared prompt prefix) is in the radix
        cache, prefilling whatever tail is missing without occupying a
        decode slot.  Returns ``{"tokens", "computed"}`` (``computed`` = 0
        when fully cached already) or None when prefix caching is off."""
        if self.prefix_cache is None:
            return None
        tokens = tuple(tokens)[: self.max_len - 1]
        if self.paged_kv:
            # only whole pages are shareable: a partial page would be
            # rewritten by the owner's decode — align the warm target down
            tokens = tokens[: len(tokens) - len(tokens) % self.page_size]
        if len(tokens) < 2:
            return None
        fut = asyncio.get_running_loop().create_future()
        task = _PrefillTask(tokens=tokens, done=fut)
        trz = current_tracer()
        if trz is not None:
            task.trz = trz
            task.span = trz.begin("warm_prefix", cat="serving.prefix",
                                  tokens=len(tokens))
            self._tracers.append(trz)
        self._warm_waiting.append(task)
        self._wake_event().set()
        self.ensure_running()
        try:
            computed = await fut
        finally:
            if task.span is not None:
                trz.end(task.span)
                self._tracers.remove(trz)
        return {"tokens": len(tokens), "computed": computed}

    def reset_prefix_cache(self):
        """Drop all cached prefixes and memoized assemblies (keeps the
        budget and the compiled prefill shapes) — benchmarking /
        tenant-isolation hook."""
        if self.prefix_cache is not None:
            if self.paged_kv:
                # page ownership is ref-counted: drop what nobody pins;
                # in-flight pinned paths drain normally
                self.prefix_cache.drop_unpinned()
                self._update_page_gauges()
            else:
                self.prefix_cache = PrefixCache(self._seq_axes,
                                                self.prefix_cache.budget)
        self._pad_memo.clear()

    def ensure_running(self):
        if self._task is None or self._task.done():
            self._stop = False
            self._task = asyncio.get_running_loop().create_task(
                self._loop())
            self._task.add_done_callback(self._on_loop_done)

    def _on_loop_done(self, task):
        if task.cancelled():
            return
        exc = task.exception()
        if exc is None:
            # quiesce raced a submission: restart so nothing strands
            if not self._stop and (not self.queue.empty()
                                   or self._warm_waiting or self._pending
                                   or self._wait_pages):
                self.ensure_running()
            return
        # surface scheduler failures to every waiting client; release
        # prefix-cache pins, page refs, and slots so a crash leaks nothing
        for t in self._pending + self._warm_waiting:
            fut = t.done if t.req is None else t.req.done
            if fut is not None and not fut.done():
                fut.set_exception(exc)
            self._release(t)
            if t.req is not None and t.slot >= 0:
                if self.paged_kv:
                    self._free_slot_paged(t.slot)
                else:
                    self.free_slots.append(t.slot)
            elif self.paged_kv and t.fresh_ids:
                self.allocator.decref(t.fresh_ids)  # starved warm task
        self._pending.clear()
        self._warm_waiting.clear()
        for slot, req in list(self.active.items()):
            if req.done and not req.done.done():
                req.done.set_exception(exc)
            if self.paged_kv:
                self.live[slot] = False
                del self.active[slot]
                self._free_slot_paged(slot)
        for req in self._wait_pages:
            if req.done and not req.done.done():
                req.done.set_exception(exc)
        self._wait_pages.clear()
        while not self.queue.empty():
            req = self.queue.get_nowait()
            if req.done and not req.done.done():
                req.done.set_exception(exc)

    async def stop(self):
        self._stop = True
        self._wake_event().set()
        if self._task is not None:
            await self._task

    # -- stats ----------------------------------------------------------------

    @property
    def prefill_compilations(self) -> int:
        """Distinct prefill shapes traced (== XLA compilations)."""
        return len(self.prefill_shapes)

    @property
    def prefill_shape_bound(self) -> int | None:
        """Bucketing-guaranteed ceiling on prefill compilations: every
        call pads to a (prefix-bucket, suffix-bucket) pair, so at most
        (|buckets|+1) · |buckets| shapes exist no matter how many distinct
        prompt lengths traffic brings.  None on the exact-length path."""
        if not self._paged:
            return None
        return (len(self._buckets) + 1) * len(self._buckets)

    @property
    def page_op_shape_bound(self) -> int:
        """Ceiling on paged gather/fill compilations: one shape per
        (op, bucket) pair."""
        return 2 * len(self._buckets)

    def stats(self) -> dict:
        out = {
            "steps": self.steps,
            "decode_tokens": self.decode_tokens,
            "max_occupancy": self.max_occupancy,
            "host_syncs": self.host_syncs,
            "prefill_compilations": self.prefill_compilations,
            "prefill_shape_bound": self.prefill_shape_bound,
            "prefill_buckets": list(self._buckets),
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_tokens_reused": self.prefill_tokens_reused,
            "kv_layout": self.kv_layout,
            "kv_admit_copies": self.kv_admit_copies,
            "prefix_cache": self.prefix_cache.stats()
            if self.prefix_cache is not None else None,
        }
        if self.paged_kv:
            out["paged"] = {
                "page_size": self.page_size,
                "num_pages": self.num_pages,
                "pages_free": self.allocator.free_count,
                "page_faults": self.allocator.page_faults,
                "page_evicts": self.allocator.page_evicts,
                "admit_stalls": self.admit_stalls,
                "page_op_shapes": len(self.page_op_shapes),
                "page_op_shape_bound": self.page_op_shape_bound,
            }
        return out

    # -- prefill --------------------------------------------------------------

    def _bucket(self, n: int, *, allow_zero=False) -> int:
        if allow_zero and n == 0:
            return 0
        for b in self._buckets:
            if n <= b:
                return b
        return n  # beyond max_len: caller's problem, keep it exact

    def _run_prefill(self, seg, prefix_kv, prefix_len, prefix_key=()):
        """Prefill `seg` (a prompt suffix) given `prefix_len` tokens of
        already-computed KV.  Pads both sides to buckets so compilations
        stay bounded; returns (boundary logits [1,V], suffix KV of
        exactly len(seg) positions)."""
        L = len(seg)
        Sb = self._bucket(L)
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :L] = seg
        if prefix_kv is None:
            prefix_kv = self._empty_prefix
        Tb = self._bucket(prefix_len, allow_zero=True)
        memo_key = (prefix_key, Tb) if prefix_key else None
        pfx = self._pad_memo.get(memo_key) if memo_key else None
        if pfx is None:
            pfx = tree_pad_to(prefix_kv, self._seq_axes, Tb)
            if memo_key:
                if len(self._pad_memo) >= self._pad_memo_cap:
                    self._pad_memo.pop(next(iter(self._pad_memo)))
                self._pad_memo[memo_key] = pfx
        self.prefill_shapes.add((Tb, Sb))
        logits, cache = self._prefill_px(
            self.params, jnp.asarray(toks), pfx,
            jnp.asarray(prefix_len, jnp.int32),
            jnp.asarray(L - 1, jnp.int32))
        self.prefill_chunks += 1
        self.prefill_tokens_computed += L
        if Sb != L:
            cache = tree_slice(cache, self._seq_axes, 0, L)
        return logits, cache

    def _prefill_start(self, task: _PrefillTask) -> bool:
        """First-touch setup for a pending task.  On the paged path this
        only ever sees warm tasks (requests match + allocate inside
        ``_page_admit``); returns False when a paged warm task can't get
        pages (best-effort: warming is an optimization, never an error)."""
        task.started = True
        if self.prefix_cache is None:
            return True
        # a request must prefill ≥1 suffix token for its first-step logits
        limit = len(task.tokens) - (0 if task.req is None else 1)
        if limit <= 0:
            return True
        matched, kv, handle = self.prefix_cache.match_and_pin(
            task.tokens[:limit])
        task.matched = task.covered = matched
        task.handle = handle
        task.pinned_in = self.prefix_cache
        if self.paged_kv:
            mpages = kv  # paged trie returns page ids, not KV
            n_fresh = (len(task.tokens) - matched) // self.page_size
            fresh = self._alloc_pages(n_fresh)
            if fresh is None:
                return False
            task.fresh_ids = fresh
            task.page_row = list(mpages) + fresh
            task.acc = self._gather_matched(mpages, matched,
                                            task.tokens[:matched]) \
                if matched else None
        else:
            task.acc = kv
        self.prefill_tokens_reused += matched
        # prefix-cache hit depth, on the request (or warm-task) span
        sp = task.req.span if task.req is not None else task.span
        if sp is not None:
            sp.attrs["prefix_matched"] = matched
        return True

    def _release(self, task: _PrefillTask):
        # release into the instance that was pinned — reset_prefix_cache
        # may have swapped self.prefix_cache while this task was in flight
        if task.handle is not None:
            task.pinned_in.release(task.handle)
            task.handle = None

    def _prefill_step(self):
        """Run one prefill chunk for the oldest pending prompt (called
        between decode steps: iteration-level scheduling)."""
        task = self._pending[0]
        if task.req is not None and task.req.abandoned:
            self._pending.pop(0)
            self._release(task)
            if self.paged_kv:
                self._free_slot_paged(task.slot)
            else:
                self.free_slots.append(task.slot)
            return
        if not task.started and not self._prefill_start(task):
            # paged warm task starved of pages: complete best-effort
            self._pending.pop(0)
            self._release(task)
            if task.done is not None and not task.done.done():
                task.done.set_result(0)
            return
        n = len(task.tokens)
        if task.covered >= n:  # warm task fully served by the cache
            self._pending.pop(0)
            self._finalize(task)
            return
        chunk = n - task.covered
        if self.prefill_chunk:
            chunk = min(chunk, self.prefill_chunk)
        seg = task.tokens[task.covered:task.covered + chunk]
        # ends at enqueue: the chunk's device time is in the profiler's
        # trace (and in the wait of the decode step queued behind it)
        with self._span("prefill.chunk", "serving.prefill",
                        f"slot:{task.slot}" if task.slot >= 0
                        else "prefill",
                        new=chunk, cached=task.covered) as psp:
            if psp is not None:
                owner = task.req.span if task.req is not None \
                    else task.span
                psp.attrs["request"] = owner.span_id if owner else 0
            logits, kvseg = self._run_prefill(
                seg, task.acc, task.covered,
                prefix_key=task.tokens[:task.covered])
        task.acc = kvseg if task.acc is None \
            else tree_concat([task.acc, kvseg], self._seq_axes)
        task.covered += chunk
        task.last_logits = logits
        if task.covered >= n:
            self._pending.pop(0)
            self._finalize(task)

    def _finalize(self, task: _PrefillTask):
        if self.paged_kv:
            self._finalize_paged(task)
            return
        if self.prefix_cache is not None and task.covered > task.matched:
            self.prefix_cache.insert(task.tokens[:task.covered], task.acc)
        self._release(task)
        if task.req is None:  # warm task
            if task.done is not None and not task.done.done():
                task.done.set_result(task.covered - task.matched)
            return
        req = task.req
        if req.abandoned:  # cancelled while its chunks ran
            self.free_slots.append(task.slot)
            return
        slot = task.slot
        seg = tree_pad_to(task.acc, self._seq_axes,
                          self._bucket(task.covered))
        self.cache = self._splice(self.cache, seg,
                                  jnp.asarray(slot, jnp.int32))
        self.kv_admit_copies += 1
        self._begin_decode(req, slot, task.last_logits)

    def _finalize_paged(self, task: _PrefillTask):
        """Scatter freshly computed KV into this task's fresh pages and
        publish the page-aligned prefix to the trie.  Matched pages are
        *never* written or copied — the slot's page table already points
        at them (zero-copy sharing); decode only ever writes the final,
        unshared partial page."""
        ps = self.page_size
        m_pages = task.matched // ps
        if task.covered > task.matched:
            n_fill = -(-task.covered // ps) - m_pages
            nb = self._bucket(task.covered - task.matched) // ps
            seg = tree_slice(task.acc, self._seq_axes, task.matched,
                             task.covered)
            seg = tree_pad_to(seg, self._seq_axes, nb * ps)
            ids = task.page_row[m_pages:m_pages + n_fill] \
                + [0] * (nb - n_fill)
            self.page_op_shapes.add(("fill", nb))
            with maybe_span("page.fill", cat="serving.paging",
                            track=self._tr("paging"), pages=n_fill):
                self.kv_pages = self._page_fill(
                    self.kv_pages, seg, jnp.asarray(ids, jnp.int32))
        if self.prefix_cache is not None:
            aligned = (task.covered // ps) * ps
            if aligned > 0:
                self.prefix_cache.insert(task.tokens[:aligned],
                                         task.page_row[:aligned // ps])
        self._release(task)
        if task.req is None:  # warm task: pages live on via the trie refs
            if task.fresh_ids:
                self.allocator.decref(task.fresh_ids)
            self._update_page_gauges()
            if task.done is not None and not task.done.done():
                task.done.set_result(task.covered - task.matched)
            return
        req = task.req
        if req.abandoned:  # cancelled while its chunks ran
            self._free_slot_paged(task.slot)
            return
        row = task.page_row
        self._page_table[task.slot, :] = 0
        self._page_table[task.slot, :len(row)] = row
        self._table_dirty = True
        self._begin_decode(req, task.slot, task.last_logits)

    def _begin_decode(self, req: Request, slot: int, logits):
        tok = int(self._host(self._sample(logits, req))[0])
        req.out_tokens.append(tok)
        req.first_token_at = time.perf_counter()
        self._cur_host[slot, 0] = tok
        self._pos_host[slot] = len(req.prompt_tokens)
        self._slots_dirty = True
        self.live[slot] = True
        self.active[slot] = req

    def _admit_exact(self, req: Request, slot: int):
        """Exact-length one-shot prefill (recurrent/hybrid/enc_dec/int8-KV
        models, whose state is not positionally sliceable)."""
        prompt = jnp.asarray([req.prompt_tokens], jnp.int32)
        self.prefill_shapes.add((0, len(req.prompt_tokens)))
        self.prefill_tokens_computed += len(req.prompt_tokens)
        self.prefill_chunks += 1
        logits, pcache = self._prefill_exact(self.params, {"tokens": prompt})
        self.cache = jax.tree.map(
            lambda cur, new: _write_slot_cache(cur, new, slot),
            self.cache, pcache)
        self.kv_admit_copies += 1
        self._begin_decode(req, slot, logits)

    def _sample(self, logits, req):
        if req.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._rng, k = jax.random.split(self._rng)
        return sample_tokens(k, logits, temperature=req.temperature)

    # -- scheduler -------------------------------------------------------------

    def _drain_queue(self):
        if self._warm_waiting:
            self._pending.extend(self._warm_waiting)
            self._warm_waiting.clear()
        if self.paged_kv:
            self._drain_queue_paged()
            return
        while self.free_slots and not self.queue.empty():
            req = self.queue.get_nowait()
            if req.abandoned:  # cancelled while queued
                continue
            req.started_at = time.perf_counter()
            slot = self.free_slots.pop()
            req.slot = slot
            self._note_admit(req, slot)
            if self._paged:
                self._pending.append(_PrefillTask(
                    tokens=tuple(req.prompt_tokens), req=req, slot=slot))
            else:
                self._admit_exact(req, slot)

    def _note_admit(self, req: Request, slot: int):
        if req.span is not None:
            req.span.attrs["slot"] = slot
            req.span.attrs["queue_s"] = req.started_at - req.submitted_at
            req.trz.event("admit", cat="serving.admit",
                          parent=req.span, track=self._tr(f"slot:{slot}"),
                          slot=slot)

    # -- paged admission -------------------------------------------------------

    def _drain_queue_paged(self):
        """Admit in FIFO order under *page* backpressure: a request that
        can't get its pages parks at the head of ``_wait_pages`` and
        admission stops (no overtaking — later smaller requests would
        starve it).  Pages free up as decode retires slots or the trie
        evicts, and the loop retries every pass."""
        while self.free_slots and (self._wait_pages
                                   or not self.queue.empty()):
            req = self._wait_pages.pop(0) if self._wait_pages \
                else self.queue.get_nowait()
            if req.abandoned:  # cancelled while queued/stalled
                continue
            task = self._page_admit(req)
            if task is None:
                self._wait_pages.insert(0, req)
                return
            req.started_at = time.perf_counter()
            self._note_admit(req, task.slot)
            self._pending.append(task)

    def _page_admit(self, req: Request) -> _PrefillTask | None:
        """Match the radix trie, then *eagerly* allocate every page the
        request can ever touch (prompt + max_new, clamped to max_len):
        admission is the only OOM point, decode never faults.  On a trie
        hit the matched page ids go straight into the slot's page table —
        zero KV bytes move."""
        tokens = tuple(req.prompt_tokens)
        n = len(tokens)
        matched, mpages, handle = 0, (), None
        if self.prefix_cache is not None:
            # n-1: ≥1 suffix token must prefill for first-step logits
            matched, mpages, handle = self.prefix_cache.match_and_pin(
                tokens[:n - 1])
        total = min(n + req.max_new_tokens, self.max_len)
        need = -(-total // self.page_size) - matched // self.page_size
        with maybe_span("page.alloc", cat="serving.paging",
                        track=self._tr("paging"),
                        need=need, matched_pages=matched // self.page_size):
            fresh = self._alloc_pages(need)
        if fresh is None:
            if handle is not None:
                self.prefix_cache.release(handle)
            self.admit_stalls += 1
            if req.trz is not None:
                req.trz.event("page.stall", cat="serving.paging",
                              parent=req.span, track=self._tr("paging"),
                              need=need)
            return None
        # the slot takes its own ref on shared pages — the trie may evict
        # its copy of the path while this request still decodes
        self.allocator.incref(mpages)
        row = list(mpages) + fresh
        slot = self.free_slots.pop()
        req.slot = slot
        self._slot_pages[slot] = row
        # the page-table row is NOT installed yet: until _begin_decode the
        # batched decode step still issues a stale-position write for this
        # slot, which must land in the scratch page — installing the row
        # now would let it corrupt a *shared* matched page
        task = _PrefillTask(tokens=tokens, req=req, slot=slot,
                            started=True, matched=matched, handle=handle,
                            pinned_in=self.prefix_cache, page_row=row,
                            fresh_ids=fresh)
        task.covered = matched
        task.acc = self._gather_matched(mpages, matched,
                                        tokens[:matched]) \
            if matched else None
        self.prefill_tokens_reused += matched
        self._update_page_gauges()
        if req.span is not None:
            req.span.attrs["prefix_matched"] = matched
        return task

    def _alloc_pages(self, need: int) -> list | None:
        """Allocate ``need`` pages, reclaiming trie LRU leaves on a fault;
        None when even eviction can't cover it (caller stalls)."""
        if need <= 0:
            return []
        a = self.allocator
        if a.free_count < need:
            a.note_fault()
            if self.prefix_cache is not None:
                with maybe_span("page.reclaim", cat="serving.paging",
                                track=self._tr("paging"), need=need):
                    self.prefix_cache.reclaim(need)
        ids = a.alloc(need)
        self._update_page_gauges()
        return ids

    def _gather_matched(self, mpages, matched: int, key_tokens):
        """Materialize matched pages as a contiguous prefix view for the
        prefill kernel (bucketed + memoized like `_run_prefill`'s pad
        path, so a fan-out burst gathers its shared prefix once).  The
        memo stores a *copy*, so entries keyed by tokens can never go
        stale even if the source pages are later evicted and recycled."""
        tb = self._bucket(matched)
        key = (key_tokens, tb)
        pfx = self._pad_memo.get(key)
        if pfx is None:
            nb = tb // self.page_size
            ids = list(mpages) + [0] * (nb - len(mpages))
            self.page_op_shapes.add(("gather", nb))
            with maybe_span("page.gather", cat="serving.paging",
                            track=self._tr("paging"), pages=len(mpages)):
                pfx = self._page_gather(self.kv_pages,
                                        jnp.asarray(ids, jnp.int32))
            if len(self._pad_memo) >= self._pad_memo_cap:
                self._pad_memo.pop(next(iter(self._pad_memo)))
            self._pad_memo[key] = pfx
        return tree_slice(pfx, self._seq_axes, 0, matched)

    def _free_slot_paged(self, slot: int):
        row = self._slot_pages.pop(slot, None)
        if row:
            self.allocator.decref(row)
            self._update_page_gauges()
        self._page_table[slot, :] = 0
        self._table_dirty = True
        self.free_slots.append(slot)

    def _update_page_gauges(self):
        ev = self.prefix_cache.evictable_pages() \
            if self.prefix_cache is not None else 0
        free = self.allocator.free_count
        self.allocator.set_pinned(self.num_pages - free - ev)

    def _finish(self, slot):
        req = self.active.pop(slot)
        req.finished_at = time.perf_counter()
        self.live[slot] = False
        if self.paged_kv:
            self._free_slot_paged(slot)
        else:
            self.free_slots.append(slot)
        if not req.done.done():
            req.done.set_result(req.out_tokens)

    def _retire_finished(self):
        for slot in list(self.active):
            req = self.active[slot]
            last = req.out_tokens[-1] if req.out_tokens else None
            if (req.abandoned  # hedge loser / dropped client: free the slot
                    or len(req.out_tokens) >= req.max_new_tokens
                    or (self.eos_token is not None
                        and last == self.eos_token)
                    or self._pos_host[slot] >= self.max_len - 1):
                self._finish(slot)

    def _host(self, x) -> np.ndarray:
        """Blocking device->host read of ``x`` (a writable copy).  Every
        such read of the loop goes through here and is counted
        (``host_syncs``, the ``syncs`` of the pass's ``loop.iter``)."""
        self.host_syncs += 1
        if self._c_syncs is not None:
            self._c_syncs.inc()
        return np.array(x)

    def _upload_slots(self):
        """Upload the host's positions and current tokens to the device
        arrays the decode step reads.  ``jnp.array`` copies: on the CPU a
        zero-copy ``asarray`` would alias the buffers the host goes on
        writing."""
        self.positions = jnp.array(self._pos_host)
        self.cur_tokens = jnp.array(self._cur_host)
        self._slots_dirty = False

    def _decode_once(self):
        with self._span("decode.step", "serving.decode", "decode",
                        occupancy=len(self.active)) as dsp:
            if dsp is not None:
                dsp.attrs["slots"] = sorted(self.active)
            if self._slots_dirty:
                self._upload_slots()
            if self.paged_kv:
                if self._table_dirty:
                    self._table_dev = jnp.asarray(self._page_table)
                    self._table_dirty = False
                logits, self.kv_pages = self._decode_paged(
                    self.params, self.kv_pages, self.cur_tokens,
                    self.positions, self._table_dev)
            else:
                logits, self.cache = self._decode(
                    self.params, self.cache, self.cur_tokens,
                    self.positions)
            n = len(self.active)
            self.steps += 1
            self.occupancy_sum += n
            self.max_occupancy = max(self.max_occupancy, n)
            if self._h_occupancy is not None:
                self._h_occupancy.observe(n)
            stochastic = any(r.temperature > 0.0
                             for r in self.active.values())
            if stochastic:
                # one RNG split + one device call + one host transfer for
                # the whole batch, however many slots sample
                self._rng, k = jax.random.split(self._rng)
                temps = np.zeros((self.max_slots,), np.float32)
                for slot, req in self.active.items():
                    temps[slot] = req.temperature
                toks = self._sample_all(k, logits, jnp.asarray(temps))
            else:
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # the step's one wait for the device; it also waits for the
            # prefill chunks enqueued ahead of the step in this pass
            with self._span("device.wait", "serving.decode", "decode",
                            parent=dsp) as wsp:
                if wsp is not None:
                    wsp.attrs["prefill_queued"] = \
                        self.prefill_chunks - self._pass.chunks0
                nxt = self._host(toks)
            for slot, req in self.active.items():
                tok = int(nxt[slot])
                req.out_tokens.append(tok)
                self.decode_tokens += 1
                self._cur_host[slot, 0] = tok
                self._pos_host[slot] += 1
            self._upload_slots()

    # -- the loop -------------------------------------------------------------

    def _span(self, name: str, cat: str, track: str, *, parent=None,
              **attrs):
        """A span of the current pass of the loop (a child of its
        ``loop.iter`` unless ``parent`` is given), or the shared null
        context when the pass is not traced."""
        p = self._pass
        if p is None:
            return _UNTRACED
        return _loop_span(p.trz, name, cat,
                          p.span if parent is None else parent,
                          self._tr(track), attrs)

    def _run_pass(self) -> bool:
        """One pass of the scheduler: admit, one prefill chunk, one
        decode step for the whole batch, retire.  Returns whether it did
        any work."""
        with self._span("queue.drain", "serving.loop", "engine"):
            self._drain_queue()
        progressed = False
        if self._pending:
            # one prefill chunk between decode steps: a long admit
            # yields to the live batch instead of freezing it
            self._prefill_step()
            progressed = True
        if self.active:
            self._decode_once()
            with self._span("retire", "serving.loop", "engine"):
                self._retire_finished()
            progressed = True
        return progressed

    def _run_traced_pass(self, trz) -> bool:
        """``_run_pass`` inside a ``loop.iter`` span: detached, on the
        engine's track, ending with the requests still ``live``, whether
        it ``decoded`` and the host ``syncs`` it made."""
        syncs, steps = self.host_syncs, self.steps
        with _loop_span(trz, "loop.iter", "serving.loop", DETACHED,
                        self._tr("engine"), {}) as sp:
            self._pass = _Pass(trz, sp, self.prefill_chunks)
            try:
                progressed = self._run_pass()
            finally:
                self._pass = None
            sp.attrs.update(live=len(self.active),
                            decoded=self.steps - steps,
                            syncs=self.host_syncs - syncs)
        return progressed

    async def _loop(self):
        while not self._stop:
            if self._tracers:
                progressed = self._run_traced_pass(self._tracers[-1])
            else:
                progressed = self._run_pass()
            if progressed:
                await asyncio.sleep(self.step_sleep or 0)
                continue
            # idle: sleep until a submission wakes us (no busy-polling);
            # quiesce after idle_quiesce_s — restarted on next request
            wake = self._wake_event()
            wake.clear()
            if not self.queue.empty() or self._warm_waiting:
                continue
            try:
                await asyncio.wait_for(wake.wait(), self.idle_quiesce_s)
            except asyncio.TimeoutError:
                # _wait_pages while otherwise idle can't happen under the
                # generate() page-granularity reject (anything admitted
                # retires and frees its pages), but don't quiesce past a
                # stalled request: keep the loop alive to retry
                if self.queue.empty() and not self._warm_waiting \
                        and not self._pending and not self._wait_pages:
                    return


@dataclass
class _Pass:
    """A traced pass of the loop: its tracer, its ``loop.iter`` span,
    and the prefill chunks enqueued before it began."""

    trz: object
    span: object
    chunks0: int


@contextlib.contextmanager
def _loop_span(trz, name, cat, parent, track, attrs):
    """One span of the engine's loop.  While a ``jax.profiler`` session
    records, it also enters a ``TraceAnnotation`` of the same name, so a
    profile shows the loop on the profiler's own clock beside the device
    ops."""
    with trz.span(name, cat=cat, track=track, parent=parent, **attrs) as sp:
        if jax.profiler.TraceAnnotation.is_enabled():
            with jax.profiler.TraceAnnotation(name):
                yield sp
        else:
            yield sp


def _write_slot_cache(full, new, slot):
    """full: [L?, max_slots, ...]; new: [L?, 1, ...] — write batch slot.

    Works for both stacked-layer leading dims and flat caches because the
    batch dim is identified from `new` having size 1 there."""
    # find the batch axis: the axis where new has 1 and full has max_slots
    for ax in range(new.ndim):
        if new.shape[ax] == 1 and full.shape[ax] != new.shape[ax]:
            idx = [slice(None)] * full.ndim
            idx[ax] = slice(slot, slot + 1)
            if new.shape[ax + 1:] != full.shape[ax + 1:]:
                # capacity axis may also differ (prompt < max_len): pad
                pads = [(0, f - n) if i > ax else (0, 0)
                        for i, (f, n) in enumerate(zip(full.shape,
                                                       new.shape))]
                new = jnp.pad(new, pads)
            return full.at[tuple(idx)].set(new.astype(full.dtype))
    return full  # fully matching leaf (e.g. shared cross-attention memory)
