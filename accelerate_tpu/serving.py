"""Continuous batching: a slot-based serving engine over the KV-cache decode.

Static batching (``generate``) decodes one fixed batch to completion —
short requests wait for the longest one, and new requests wait for the
whole batch. Continuous batching keeps a fixed pool of ``num_slots``
sequences in flight: finished sequences retire and free their slot
immediately, queued prompts prefill into free slots, and ONE jitted
vmapped decode step advances every active slot per tick (the vLLM-style
serving loop, shaped for XLA: all programs have static shapes, so the
engine compiles a handful of programs once and replays them forever).

No reference analogue (the reference delegates generation entirely);
parity-plus. Design notes:

* per-slot KV caches are the model's ordinary cache pytree with a leading
  slot axis; the decode tick is ``jax.vmap`` of the single-sequence step,
  so per-slot positions/cache indices need NO model changes;
* prompt prefill pads up to a size bucket (one compile per bucket). What
  the padded tail does depends on the leaf. **K/V and latent rows**: it
  DOES write garbage rows at positions >= true_len — harmless by
  construction: they sit beyond the causal frontier (key_pos > q_pos
  masks them) and each decode step overwrites the next one, because the
  cache write index is reset to ``true_len`` after prefill. **Recurrent
  state** (``ops.paged_kv.STATE_LEAVES``: a state-space layer's ``ssm_state``
  and ``conv_state``, a gated short convolution's ``conv_state``; one row a
  slot and no row a token): a state after the bucket's last token would
  include the pad, so every program that runs a window tells the model
  which of its tokens are new and real (``new_span``), and the others
  leave the state as it was (ops/selective_scan.py);
* inactive slots still compute in the tick (static shapes; masking out
  their tokens is host-side bookkeeping). Their caches accumulate
  garbage that the next prefill-insert fully replaces: rows land in the
  trash sink, and a recurrent state, which ``clear_slot`` zeroed when the
  slot was freed, is stepped on (finite, never read) until ``paste_row``
  writes the next request's over it whole;
* **chunked prefill**: a prompt longer than the largest bucket streams
  through the decode path in largest-bucket-sized chunks against the
  growing cache (``cached_attention`` is the same program for S_new = 1
  and S_new = C) — so prompt length is bounded by cache capacity, not by
  the compiled bucket set, and the compile count stays O(buckets);
* **prefix caching**: :meth:`register_prefix` prefills a shared prompt
  prefix (e.g. a system prompt) ONCE and stores the row cache;
  ``submit(..., prefix_id=...)`` requests copy it and prefill only their
  suffix — the vLLM prefix-reuse win, token-exact by construction because
  the copied cache is bit-identical to what a full prefill would write
  (rows, and for a state-space layer the state at the prefix's end: paged
  blocks are aliased, the state is each request's own copy);
* **paged KV cache** (``paged_block_size=...``): slot caches live in one
  shared block pool addressed through per-slot block tables
  (:mod:`accelerate_tpu.ops.paged_kv`) instead of ``slots x max_len``
  dense rows — pool capacity is sized by expected tokens in flight
  (``pool_blocks``), admission waits when the pool is exhausted, and
  prefix blocks are refcount-shared across requests rather than copied.
  The decode tick becomes ONE batched program (per-row frontiers are
  native to the paged layout) and outputs stay token-exact vs dense;
* **token-budget continuous batching** (``scheduler=SchedulerConfig``,
  :mod:`accelerate_tpu.scheduling`): each tick spends at most
  ``token_budget`` tokens — active decodes claim theirs first, and the
  remainder streams *chunks* of pending prefills through the existing
  chunked-prefill windows, so a long prompt makes TTFT progress without
  ever stalling a running decode for its whole prefill. Priority-class
  admission, SLO-aware load shedding (structured :class:`ShedError` +
  ``shed`` events instead of silent queueing), and decode preemption
  (the youngest low-priority decode releases its slot and KV blocks,
  requeues, and resumes by prefix-style recomputation — token-exact,
  logprobs to float32 rounding) ride on the same tick loop. The default
  config is behavior-preserving: unlimited budget, one priority class, no
  shedding, no preemption.
"""

from __future__ import annotations

import bisect
import dataclasses
import logging
import math
import time
from typing import Optional

import numpy as np

from .ft.crashpoints import crash_point
from .scheduling import Scheduler, SchedulerConfig, ShedError
from .telemetry.trace import phase, phased

logger = logging.getLogger(__name__)


def _jax():
    import jax

    return jax


def _row_axis(shape: tuple, cap: int):
    """Index of a cache leaf's position-row axis (the one sized to the
    model's cache capacity), or None for non-row leaves (write-index
    scalars). K/V buffers are at least [B, rows, heads, dim]-shaped —
    possibly with a leading scan-over-layers axis — so the first
    ``cap``-sized axis of an ndim >= 3 leaf is the row axis."""
    if len(shape) < 3:
        return None
    for i, d in enumerate(shape):
        if d == cap:
            return i
    return None


def check_handoff_layout(row_cache) -> None:
    """KV hand-off ships per-head K/V rows. A latent (MLA) row cache — one
    ``latent`` row a token, shared by all heads — is not carried yet: say so
    instead of sizing or packing it as K/V. Neither is a recurrent state
    (:data:`ops.paged_kv.STATE_LEAVES`), which has no rows to trim."""
    from .ops.kv_cache import leaf_names

    check_no_state_leaf(row_cache, "KV hand-off (kv_handoff_dims, prefill_detached, HandoffCodec)")
    check_no_summary_leaf(row_cache, "KV hand-off (kv_handoff_dims, prefill_detached, HandoffCodec)")
    if "latent" in leaf_names(row_cache):
        raise NotImplementedError(
            "KV hand-off (kv_handoff_dims, prefill_detached, HandoffCodec) cannot carry a latent "
            "cache yet: its rows are [kv_lora_rank + qk_rope_head_dim] latents shared by all heads, "
            "not per-head keys and values; serve latent-attention models without disaggregated prefill"
        )


def check_no_summary_leaf(row_cache, what: str) -> None:
    """``what`` ships K/V rows trimmed to a frontier; a cache that also holds
    chunk summaries (``summary_key`` / ``summary_value``: EVA's pooled keys and
    values, which the paged layout keeps in pages of their own under a second
    table) is refused by name."""
    from .ops.kv_cache import leaf_names

    if "summary_key" in leaf_names(row_cache):
        raise NotImplementedError(
            f"{what} cannot carry summary pages yet: an EVA cache holds the open window's rows and one pooled "
            "key and value for every chunk of the closed windows under a second table, not rows a token to "
            "trim and pad; serve EVA models without it"
        )


def check_no_state_leaf(row_cache, what: str) -> None:
    """``what`` ships K/V rows trimmed to a frontier; a cache with a
    recurrent-state leaf (:data:`ops.paged_kv.STATE_LEAVES`: a state-space
    layer's ``ssm_state``, a convolution's ``conv_state``; one row a slot,
    no row a token) is refused, by the leaves it holds."""
    from .ops.kv_cache import leaf_names
    from .ops.paged_kv import STATE_LEAVES

    names = leaf_names(row_cache)
    held = [name for name in STATE_LEAVES if name in names]
    if held:
        raise NotImplementedError(
            f"{what} cannot carry a recurrent state yet: {' / '.join(held)} are one row a sequence, "
            "not rows a token to trim and pad; serve models whose layers keep one without it "
            "(a failover resumes by prefix recompute, which is exact)"
        )


_NO_FOLD = np.int32(-1)  # :meth:`ServingEngine._request_key`: the key is the chain as it stands


@dataclasses.dataclass
class _Request:
    uid: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int
    out_tokens: list
    prefix_id: Optional[int] = None
    # per-request stop token-id sequences (engine eos still applies); a
    # request finishes when its generated tail equals any sequence, with
    # the stop tokens kept in the output (eos convention)
    stop_sequences: tuple = ()
    # log P(tok) for each generated token, aligned with out_tokens
    out_lps: list = dataclasses.field(default_factory=list)
    # scheduling state (accelerate_tpu.scheduling): admission class (lower
    # admits sooner), submit timestamp (queue-wait SLO + metrics), and the
    # preemption/resume carry — a preempted decode requeues with its
    # generated-so-far tokens plus its sampling key so the resumed stream
    # is token-exact (logprobs to float32 rounding: chunk windows rebuild the K/V)
    priority: int = 0
    submit_ts: float = 0.0
    preempted: bool = False
    deprioritized: bool = False
    ttft_done: bool = False
    resume_key: object = None
    # disaggregated serving (serving_fleet): a request whose prefill ran
    # on ANOTHER replica carries the handed-off KV payload; consumed once
    # at admission (a later preemption resumes by ordinary recompute)
    handoff: object = None
    # distributed-tracing context (telemetry.trace): the trace id minted
    # at submit. Rides the handoff blob and failover snapshots, so one
    # id follows the request across replicas end to end
    trace: Optional[int] = None


class ServingEngine:
    """Continuous-batching decode engine for a zoo model with the decode
    contract (``apply_fn(params, ids, positions=..., decode=True,
    cache=..., logits_at=...) -> (logits, cache)``; llama / gpt2 /
    gptneox). ``logits_at``: an int32 scalar or ``[n]`` of positions whose
    logits the caller keeps (the final norm and the output head run on
    those rows alone), ``None`` = every position. An ``apply_fn`` that
    cannot be called so is refused at construction, with a ``TypeError``
    that names the contract. The device programs the engine runs are
    built by :mod:`accelerate_tpu.serving_programs`, once, from here.

    ``prompt_buckets``: ascending prefill sizes; each distinct bucket
    compiles one prefill program. ``max_len``: cache capacity per slot
    (default: the model's ``max_position_embeddings``). Decoding is
    greedy at ``temperature=0`` (the token-exact-vs-generate setting) or
    temperature/top-k sampling with an independent per-slot key chain
    folded on the request uid (deterministic per ``seed``).

    ``paged_block_size``: enable the paged KV cache with this block size
    (rows per pool block; 16-64 keeps tables small and pool granularity
    useful). ``pool_blocks``: total pool blocks including the reserved
    trash sink (default ``num_slots * ceil(max_len / block_size) + 1``,
    i.e. dense-equivalent capacity — pass less to oversubscribe HBM and
    let admission control queue requests when the pool is full).

    **A pool and a table a kind of layer.** A model whose ``layer_types``
    name both ``sliding_attention`` and ``full_attention`` layers is served,
    under the paged layout, from two pools: the full layers keep blocks for
    the whole context under ``block_table``; the window layers keep a pool
    of their own (``window_pool_blocks``, the sink included; default
    ``num_slots * ring + 1``) under a ``window_table`` whose ``ring`` entries
    (``ops.paged_kv.ring_entries``: the pages a band spans and one) a slot
    uses as a ring, reserved at admission and freed at retirement with no
    table traffic in between. Admission waits for the scarcer pool. What is
    not built over the ring is refused by name: chunk windows, prefix reuse,
    preemption with resume, ``import_inflight`` of a request that has
    decoded, KV hand-off and export.
    """

    @phased("engine.init")
    def __init__(
        self,
        model,
        num_slots: int = 4,
        prompt_buckets=(32, 128),
        max_len: Optional[int] = None,
        eos_token_id: Optional[int] = None,
        tick_block: int = 8,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        seed: int = 0,
        paged_block_size: Optional[int] = None,
        pool_blocks: Optional[int] = None,
        window_pool_blocks: Optional[int] = None,
        telemetry_log=None,
        program_cache=None,
        scheduler=None,
        tracer=None,
    ):
        jax = _jax()
        jnp = jax.numpy
        # serving-side observability (TTFT, tokens/sec, queue depth, KV
        # utilisation, preemptions + Prometheus dump); ``telemetry_log``
        # (an EventLog) additionally mirrors snapshots into a run's JSONL
        from .telemetry.serving_metrics import ServingMetrics

        self.metrics = ServingMetrics(self, log=telemetry_log)
        self.model = model
        self.num_slots = num_slots
        self.prompt_buckets = tuple(sorted(prompt_buckets))
        self._chunk = max(self.prompt_buckets)  # a chunk window's width: the largest bucket
        self.max_len = max_len or model.config.max_position_embeddings
        from .telemetry.eventlog import EventLog

        self._log = telemetry_log if telemetry_log is not None else EventLog(None)
        # request tracing (telemetry.trace.Tracer, usually the fleet
        # router's shared instance): segments are recorded at admission,
        # prefill windows, decode ticks, preemption/resume, and retire.
        # None disables tracing with zero overhead beyond these guards.
        self.tracer = tracer
        # Compile management (docs/usage_guides/compilation.md): EVERY engine program goes through one
        # ProgramCache (serving_programs.ctx_jit): construction compiles nothing, and a persistent store
        # (``program_cache=`` or ``ACCELERATE_COMPILE_CACHE_DIR``) spares a new replica the compiles
        if program_cache is None:
            from .aot import ProgramCache

            program_cache = ProgramCache.from_env(log=self._log, name="serving")
        self._pc = program_cache
        # Scheduling policy (accelerate_tpu.scheduling): accepts a
        # SchedulerConfig, a Scheduler, or anything with
        # ``to_scheduler_config()`` (utils.ServingSchedulerKwargs). The
        # default is behavior-preserving: unlimited budget, one priority
        # class, no shedding, no preemption.
        if scheduler is None:
            scheduler = SchedulerConfig()
        if hasattr(scheduler, "to_scheduler_config"):
            scheduler = scheduler.to_scheduler_config()
        self._sched = scheduler if isinstance(scheduler, Scheduler) else Scheduler(scheduler)
        if self.max_len > model.config.max_position_embeddings:
            raise ValueError(
                f"max_len {self.max_len} exceeds the model cache "
                f"(max_position_embeddings={model.config.max_position_embeddings})"
            )
        if self._chunk > self.max_len:
            raise ValueError(
                f"prompt bucket {self._chunk} exceeds the slot cache "
                f"(max_len={self.max_len})"
            )
        if tick_block < 1:
            raise ValueError(f"tick_block must be >= 1, got {tick_block}")
        if (pool_blocks is not None or window_pool_blocks is not None) and paged_block_size is None:
            raise ValueError("pool_blocks and window_pool_blocks require paged_block_size (paged mode)")
        self.eos_token_id = eos_token_id
        self.tick_block = tick_block
        self._seed = seed

        # Cache layout: dense = leading slot axis over the per-row cache
        # pytree (each slot reserves max_len rows); paged = one shared
        # block pool + per-slot block tables (ops/paged_kv.py) — same
        # decode roofline, pool capacity decoupled from slots x max_len.
        self.paged = paged_block_size is not None
        self._pcfg = None
        # ``(window, chunk)`` for a model whose attention reads an ALIGNED window and pooled chunks before it (EVA,
        # ``attention_class == "eva"``), under the paged layout; None for every other engine
        self._aligned: Optional[tuple] = None
        self._tick_windows = (0, 0, 0, 0)  # rows attended, context rows, chunks pooled, windows closed: this tick's
        # entries of a slot's ring in the window layers' table, for a model with window AND full attention layers
        # under the paged layout (a pool and a table a kind of layer); None for every other engine
        self._ring: Optional[int] = None
        self._tick_window_rows = 0  # rows the window layers' band held for this tick's kept steps: min(t + 1, window)
        if self.paged:
            from .ops.paged_kv import BlockAllocator, PagedConfig, paged_mode

            bs_ = int(paged_block_size)
            if bs_ < 1:
                raise ValueError(f"paged_block_size must be >= 1, got {paged_block_size}")
            # table width follows the MODEL's cache horizon: the zoo's
            # cached_attention declares [B, ceil(max_position_embeddings /
            # bs)] tables regardless of the engine's (possibly smaller)
            # max_len — but reservations and the default pool are budgeted
            # by max_len, which submit() enforces
            self._mb = -(-model.config.max_position_embeddings // bs_)
            nb = int(pool_blocks) if pool_blocks is not None else num_slots * (-(-self.max_len // bs_)) + 1
            layer_types = getattr(model.config, "layer_types", None) or ()
            by_kind = {}
            if "sliding_attention" in layer_types and "full_attention" in layer_types:
                by_kind = self._init_ring(model.config, bs_, window_pool_blocks)
            elif window_pool_blocks is not None:
                raise ValueError("window_pool_blocks is the window layers' pool of a model with window and full attention layers")
            self._pcfg = PagedConfig(block_size=bs_, num_blocks=nb, **by_kind)
            self._alloc = BlockAllocator(nb)
            self._shared_refs: dict[int, int] = {}  # prefix block id -> refcount
            # per-slot {table entry index -> pool block id}: owned blocks
            # are freed at retirement OR when the sliding window expires
            # them; shared (prefix) entries only drop a refcount
            self._slot_blocks: list[dict] = [{} for _ in range(num_slots)]
            self._slot_shared: list[dict] = [{} for _ in range(num_slots)]
            self._slot_table = [np.zeros((self._mb,), np.int32) for _ in range(num_slots)]
            # windowed models never read keys <= frontier - W, so their
            # pool cost is O(window + max_new), not O(total): below-band
            # entries start as trash and blocks expire behind the frontier.
            # Per-layer attention kinds (Gemma2 alternating local/global)
            # disable the recycling: a full_attention layer reads EVERY
            # position, so no block ever becomes dead
            # (with a pool and a table a kind, ``_init_ring``, the window layers' ring recycles by itself and
            # the full layers' table holds the whole context)
            self._window = getattr(model.config, "sliding_window", None)
            if any(t != "sliding_attention" for t in layer_types):
                self._window = None
            if getattr(model.config, "attention_class", None) == "eva":
                self._init_aligned(model.config, bs_)
            with paged_mode(self._pcfg):
                _, pcache = jax.eval_shape(
                    lambda p, i, pos: model.apply_fn(p, i, positions=pos, decode=True, cache=None),
                    model.params,
                    jnp.zeros((num_slots, 1), jnp.int32),
                    jnp.zeros((num_slots, 1), jnp.int32),
                )
            self.slot_caches = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), pcache)

        # ---- the programs (compiled once each, on first use): accelerate_tpu/serving_programs.py ----
        from .serving_programs import EnginePrograms

        programs = EnginePrograms(
            model, temperature=temperature, top_k=top_k, tick_block=tick_block, prompt_buckets=self.prompt_buckets,
            paged_config=self._pcfg, program_cache=self._pc, trace_ctx=self._trace_ctx, tick_args=self._tick_args,
            on_bucket_build=self._note_bucket_compile,
        )
        self._prefill, self._chunk_cold, self._chunk_warm = programs.prefill, programs.chunk_cold, programs.chunk_warm
        self._sample_at, self._reset_idx = programs.sample_at, programs.reset_idx
        self._feed_first_token, self._decode_tick = programs.feed_first_token, programs.decode_tick
        self._insert, self._paste, self._paste_blocks = programs.insert, programs.paste_row, programs.paste_blocks
        self._clear_slots, self._set_table = programs.clear_slots, programs.set_table_row
        # name -> serving_programs.Program (raw function, sample arguments, trace contexts): perf_check() and
        # numerics_check() roofline the real prefill / decode jaxprs from it without compiling anything
        self._perf_programs = programs.described
        self._row_template = programs.row_template  # serving_programs.row_template: one sequence's dense cache
        # what this model's programs take beyond the decode contract (serving_programs.extra_arguments)
        self._has_state = programs.extra.new_span
        self._mask_idle_rows = programs.extra.decoding
        self._steps_idle_state = programs.extra.steps_idle_state
        from .ops.paged_kv import state_bytes

        self.metrics.state_bytes_per_slot = state_bytes(self._row_template)
        if not self.paged:
            self.slot_caches = jax.tree.map(lambda l: jnp.zeros((num_slots, *l.shape), l.dtype), self._row_template)

        # host-side slot state
        self.slot_req: list[Optional[_Request]] = [None] * num_slots
        self.slot_tok = np.zeros((num_slots,), np.int32)
        self.slot_pos = np.zeros((num_slots,), np.int32)
        # slot phase: None (free) | "prefill" (streaming its prompt into a
        # row cache across ticks) | "decode" (advanced by the decode tick)
        self.slot_phase: list[Optional[str]] = [None] * num_slots
        self._prefill_state: list[Optional[dict]] = [None] * num_slots
        self._prefill_order: list[int] = []  # prefilling slots, admission order
        # slot -> (first token, its logprob, the request, the fused prefill's ``dispatched`` stamp or None): fresh
        # admissions of the running tick whose first token is still on the device. The decode pass feeds each into its ``toks`` there and
        # the host reads them only once that pass is dispatched; none outlives ``step()``
        self._first_pending: dict[int, tuple] = {}
        self._tick_first_deferred = 0  # of this tick's admissions, those read after the decode dispatch
        # paged: retired slots whose ``clear_slot`` has not reached the device yet. A retirement is found in the
        # walk, when the device has nothing to do, and nothing needs its clear before the next paste into the
        # slot or the next decode tick: it goes out behind the next tick's first prefill (or with its decode
        # dispatch), all of a tick's in one program (:meth:`_flush_clears`)
        self._clear_pending: list[int] = []
        self._tick_clears_deferred = 0  # slots whose clear this tick sent behind one of its programs
        self._row_cap = None  # :meth:`_row_cache_cap`, read from the device at the first admission
        # pending requests, kept sorted by the scheduler's order key
        # (priority class, then submission order)
        self.queue: list[_Request] = []
        # uid -> ("queued"|"active"|"done", req|None): the O(1) lookup
        # behind every streaming accessor (admit/retire/cancel/preempt
        # maintain it; a linear slot+queue scan per poll() would be
        # O(requests) under thousands of queued uids)
        self._index: dict[int, tuple] = {}
        self._shed: dict[int, ShedError] = {}  # uid -> structured rejection
        self.done: dict[int, np.ndarray] = {}
        self._done_new: dict[int, np.ndarray] = {}  # uid -> generated suffix only
        self._done_lps: dict[int, np.ndarray] = {}  # uid -> per-generated-token logprobs
        self._uid = 0
        self._tick = 0  # ordinal of the running tick (the ``engine.tick`` span's count)
        self._tick_prefill_tokens = 0  # prompt tokens dispatched by this tick's prefills
        self._tick_head_rows = 0  # rows of logits those prefills computed: one a bucket prefill, a window's width
        self._tick_expert_load = (0, 0, 0, 0)
        self._tick_state_idle = 0
        self._tick_rows_skipped = 0
        self._pool_blocked = False  # last admit pass hit pool exhaustion
        self.bucket_compile_ms: dict = {}  # (kind, bucket) -> build wall ms
        # registered shared prefixes: id -> {"len", "cache", "tokens"}
        self._prefixes: dict[int, dict] = {}
        self._prefix_uid = 0
        # every fresh request's chain is ``fold_in`` of this key with its uid (:meth:`_request_key`)
        self._base_key = jax.random.key(seed)
        # independent sampling chain per slot (re-folded with the request
        # uid at each admit, so retries/new requests don't replay a chain)
        self._slot_keys = jax.vmap(jax.random.fold_in, (None, 0))(self._base_key, jnp.arange(num_slots))

    # ---- chunked prefill (host driver) ----------------------------------

    def _chunked_prefill(
        self, full_tokens: np.ndarray, row_cache=None, done_upto: int = 0, key=None, trace=None
    ):
        """Stream ``full_tokens[done_upto:]`` through the decode path in
        ``self._chunk``-sized end-aligned windows against ``row_cache``
        (None = fresh, ``done_upto`` must then be 0).

        Windows are END-aligned: a window covering new tokens ``[s, e)``
        runs as ``[max(0, e - C), e)`` — never past ``e`` — so cache writes
        stay inside ``[0, max_len)`` (a forward-padded tail would exceed it
        and ``dynamic_update_slice``'s start-clamping would silently corrupt
        the earliest rows). The overlapped head of a window recomputes
        bit-identical K/V (or latent) rows from the true tokens (positions
        are absolute), so overlap is token-exact by construction for a
        model whose cache is rows; only a ``T < C`` window has a pad tail,
        whose garbage rows sit beyond the causal frontier and are
        overwritten by decode, exactly as in bucket prefill. A recurrent
        state would count the head twice and the tail once: a model with
        state-space layers is told the window's new tokens ``[s - s_adj,
        e - s_adj)``, steps its state over those alone, and keeps the
        head's K/V rows as the cache has them (the head's hidden states
        came through layers that did not advance). Returns
        ``(next_tok | None, cache, key)`` with the cache write index reset
        to ``len(full_tokens)``; sampling happens only when ``key`` (a pair of
        :meth:`_request_key`) is given (prefix registration skips it).

        The continuous-batching scheduler does NOT call this loop — it
        advances the same :meth:`_run_window` steps one budget-claimed
        window per tick, so a long prompt never stalls running decodes."""
        jnp = _jax().numpy
        t = len(full_tokens)
        logits, s_last = None, 0
        s = done_upto
        while s < t:
            logits, row_cache, s_last, s = self._run_window(full_tokens, s, row_cache, trace=trace)
        row_cache = self._reset_idx(row_cache, jnp.int32(t))
        next_tok = lp = None
        if key is not None:
            next_tok, lp, key = self._sample_at(logits, jnp.int32(t - 1 - s_last), *key)
        return next_tok, lp, row_cache, key

    def _next_window(self, t: int, s: int):
        """Plan the next end-aligned prefill window over ``full[ s, t)``:
        ``(w, s_adj, e)`` — width = smallest bucket covering the remainder
        (a short suffix after a long prefix runs a suffix-sized program,
        not a full chunk), else the largest chunk; jit specializes per
        width, so the compile count stays O(buckets). The width is also
        the window's token-budget claim."""
        w = self._bucket_for(t - s) or self._chunk
        e = min(s + w, t)
        return w, max(0, e - w), e  # end-aligned window [s_adj, s_adj + w)

    def _run_window(self, full_tokens: np.ndarray, s: int, row_cache, trace=None):
        """Execute ONE prefill window starting at new-token offset ``s``;
        returns ``(logits, cache, s_adj, e)``. With a trace id, each
        window records one ``prefill`` span — its frontier-contiguous
        wall time plus ``dispatch_ms``, the time to enqueue the window:
        nothing here waits for the device, so there is no compute time
        to report."""
        jnp = _jax().numpy
        t = len(full_tokens)
        w, s_adj, e = self._next_window(t, s)
        window = np.zeros((1, w), np.int32)
        real = full_tokens[s_adj : s_adj + w]
        window[0, : len(real)] = real
        t0 = time.perf_counter()
        new = (jnp.int32(s - s_adj), jnp.int32(e - s_adj))
        if row_cache is None:
            logits, row_cache = self._chunk_cold(self.model.params, jnp.asarray(window), *new)
        else:
            row_cache = self._reset_idx(row_cache, jnp.int32(s_adj))
            logits, row_cache = self._chunk_warm(
                self.model.params, jnp.asarray(window), jnp.int32(s_adj), row_cache, *new
            )
        if self.tracer is not None and trace is not None:
            self.tracer.seg(
                trace, "prefill", tokens=int(w),
                dispatch_ms=round((time.perf_counter() - t0) * 1000.0, 3),
            )
        return logits, row_cache, s_adj, e

    # ---- public API ----------------------------------------------------

    def register_prefix(self, prefix_ids) -> int:
        """Prefill a shared prompt prefix ONCE; requests submitted with the
        returned ``prefix_id`` copy its KV cache and prefill only their
        suffix. The finished output includes the prefix tokens. For a model
        with state-space layers the stored row cache also holds the
        recurrent state at the prefix's end: a request's suffix windows
        start from a copy of it (blocks are aliased, state is not)."""
        if self._aligned is not None:
            raise NotImplementedError(self._aligned_refusal("prefix reuse (register_prefix)"))
        if self._ring is not None:
            raise NotImplementedError(self._ring_refusal("prefix reuse (register_prefix)"))
        toks = np.asarray(prefix_ids, np.int32).ravel()
        if len(toks) == 0:
            raise ValueError("empty prefix")
        if len(toks) + 1 > self.max_len:
            raise ValueError(
                f"prefix length {len(toks)} leaves no room in the slot cache "
                f"(max_len={self.max_len})"
            )
        _, _, cache, _ = self._chunked_prefill(toks)
        pid = self._prefix_uid
        self._prefix_uid += 1
        entry = {"len": len(toks), "cache": cache, "tokens": toks}
        if self.paged:
            # reserve the prefix's FULL blocks and write their content ONCE
            # — this registration-time paste is the canonical shared bytes
            # every aliasing request reads; admits never rewrite them (a
            # rewrite would race slots actively decoding against the
            # blocks, and cross-program recomputes of the same K/V are not
            # guaranteed bit-identical)
            bs_ = self._pcfg.block_size
            n_full = len(toks) // bs_
            # windowed models: no request can ever read below the minimum
            # band (shortest suffix is 1 token), so registering those
            # blocks would pin pool space every aliasing table sets to
            # trash anyway — a 24k-token prefix with a 4k window pins
            # O(window), not O(prefix)
            lo_min = 0
            if self._window is not None:
                lo_min = min(max(0, len(toks) + 1 - self._window + 1) // bs_, n_full)
            ids = self._alloc.alloc(n_full - lo_min)
            if ids is None:
                raise ValueError(
                    f"prefix needs {n_full - lo_min} pool blocks but only "
                    f"{self._alloc.free_count} are free; raise pool_blocks or unregister prefixes"
                )
            entry["block_ids"] = dict(zip(range(lo_min, n_full), ids))
            for bid in ids:
                self._shared_refs[bid] = 1  # registration's own reference
            if ids:
                jnp = _jax().numpy
                write_row = np.zeros((self._mb,), np.int32)  # pad -> trash sink
                for i, bid in entry["block_ids"].items():
                    write_row[i] = bid
                self.slot_caches = self._paste_blocks(self.slot_caches, cache, jnp.asarray(write_row))
        self._prefixes[pid] = entry
        return pid

    def unregister_prefix(self, prefix_id: int) -> None:
        """Release a registered prefix's device cache (each prefix pins a
        full per-row KV pytree in HBM — long-running servers should evict
        prefixes they no longer route requests to)."""
        if prefix_id not in self._prefixes:
            raise ValueError(f"unknown prefix_id {prefix_id}")
        if any(r is not None and r.prefix_id == prefix_id for r in self.slot_req) or any(
            r.prefix_id == prefix_id for r in self.queue
        ):
            raise ValueError(f"prefix_id {prefix_id} still referenced by active/queued requests")
        entry = self._prefixes[prefix_id]
        if self.paged:
            # Validate every refcount BEFORE mutating anything so a failed
            # invariant (must survive python -O) leaves the pool accounting
            # intact for diagnosis rather than half-freed.
            for bid in entry.get("block_ids", {}).values():
                refs = self._shared_refs.get(bid)
                if refs != 1:
                    raise RuntimeError(f"shared block {bid} still referenced ({refs})")
        del self._prefixes[prefix_id]
        if self.paged:
            for bid in entry.get("block_ids", {}).values():
                self._shared_refs.pop(bid)
                self._alloc.free([bid])

    def submit(
        self,
        prompt_ids,
        max_new_tokens: int = 32,
        prefix_id: Optional[int] = None,
        stop_sequences=None,
        priority: int = 0,
        trace: Optional[int] = None,
    ) -> int:
        """Queue a prompt; returns a request id resolved via :meth:`poll`.
        With ``prefix_id``, ``prompt_ids`` is the SUFFIX after the registered
        prefix (at least one token — its logits seed the first sample).
        ``stop_sequences``: per-request token-id sequences (each a list of
        ints) that end generation when they appear in the generated tail —
        the token-level analogue of vLLM's ``stop``; the matched tokens stay
        in the output like an EOS does. ``priority``: admission class —
        lower admits sooner; sheddable/preemptible classes are configured
        by the engine's :class:`~accelerate_tpu.scheduling.SchedulerConfig`.
        When the queue-depth SLO is blown, sheddable submissions raise a
        structured :class:`~accelerate_tpu.scheduling.ShedError` (or are
        demoted, with ``shed_action="deprioritize"``) instead of silently
        queueing into a blown latency target."""
        prompt = np.asarray(prompt_ids, np.int32).ravel()
        if len(prompt) == 0:
            raise ValueError("empty prompt" + (" suffix" if prefix_id is not None else ""))
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        stops = tuple(tuple(int(t) for t in s) for s in (stop_sequences or ()))
        if any(len(s) == 0 for s in stops):
            raise ValueError("empty stop sequence")
        plen = 0
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise ValueError(f"unknown prefix_id {prefix_id}; call register_prefix first")
            plen = self._prefixes[prefix_id]["len"]
        if plen + len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prefix ({plen}) + prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slot cache ({self.max_len})"
            )
        if (self._aligned is not None or self._ring is not None) and self._bucket_for(len(prompt)) is None:
            refusal = self._aligned_refusal if self._ring is None else self._ring_refusal
            raise NotImplementedError(refusal(
                f"chunk windows (a prompt of {len(prompt)} tokens, past the largest prefill bucket {self._chunk})"))
        if self.paged:
            need = self._new_blocks_for(plen, len(prompt), max_new_tokens)
            if need > self._pcfg.num_blocks - 1:
                raise ValueError(
                    f"request needs {need} pool blocks but the pool has "
                    f"{self._pcfg.num_blocks - 1}; raise pool_blocks or paged_block_size"
                )
            # (the window layers' pool holds no prefix: a head that fits it fits once the slots have drained)
            if self._ring is not None and (ring := self._ring_blocks_for(len(prompt), max_new_tokens)) > self._pcfg.window_blocks - 1:
                raise ValueError(
                    f"request needs {ring} blocks of the window layers' pool but it has "
                    f"{self._pcfg.window_blocks - 1}; raise window_pool_blocks"
                )
        with phase("engine.submit", uid=self._uid, prompt_tokens=len(prompt), queue_len=len(self.queue)):
            priority = self._admission_shed_check(int(priority), trace=trace)
            uid = self._uid
            self._uid += 1
            if self.tracer is not None:
                # a router-minted trace arrives via ``trace=``; standalone
                # engines mint their own here, after the shed gate passed
                if trace is None:
                    trace = self.tracer.start()
                self.tracer.attach(trace, uid=uid, prompt_tokens=len(prompt))
            req = _Request(
                uid, prompt, max_new_tokens, [], prefix_id, stops,
                priority=priority, submit_ts=time.monotonic(), trace=trace,
            )
            self._queue_push(req)
            self._index[uid] = ("queued", req)
            self.metrics.on_submit(uid)
            return uid

    # ---- disaggregated prefill / KV handoff (serving_fleet) -------------

    def kv_handoff_dims(self) -> tuple:
        """``(bytes_per_token, fixed_bytes)`` of this engine's dense
        per-row KV cache — the inputs
        :func:`~accelerate_tpu.analysis.costmodel.price_kv_handoff` needs
        to price a prefill→decode handoff BEFORE the prefill runs.
        Row-axis leaves (one K/V row per position) contribute per-token
        bytes; everything else (the write-index scalar) is fixed. The
        prediction and a router's post-transfer accounting
        (``handoff["wire_bytes"]``) must agree byte-for-byte."""
        jax = _jax()
        self._check_handoff()
        cap = self.model.config.max_position_embeddings
        per_tok = fixed = 0
        for leaf in jax.tree_util.tree_leaves(self._row_template):
            shape = tuple(int(d) for d in leaf.shape)
            n = 1
            for d in shape:
                n *= d
            nbytes = n * np.dtype(leaf.dtype).itemsize
            if _row_axis(shape, cap) is not None:
                per_tok += nbytes // cap
            else:
                fixed += nbytes
        return per_tok, fixed

    def _trim_row_cache(self, cache, n: int):
        """Host-side copy of a dense row cache keeping only its first
        ``n`` K/V rows — the handoff wire payload (garbage pad rows past
        the frontier never ship). Non-row leaves (the write index) pass
        through whole."""
        jax = _jax()
        cap = self.model.config.max_position_embeddings

        def trim(t, leaf):
            ax = _row_axis(tuple(int(d) for d in t.shape), cap)
            if ax is None:
                return np.asarray(leaf)
            idx = (slice(None),) * ax + (slice(0, n),)
            return np.asarray(leaf[idx])

        return jax.tree_util.tree_map(trim, self._row_template, cache)

    def _untrim_row_cache(self, cache, n: int):
        """Pad a trimmed handoff cache back to the full row template
        (zeros past row ``n`` — beyond the causal frontier by
        construction, overwritten by decode exactly like prefill pad)."""
        jax = _jax()
        jnp = jax.numpy
        cap = self.model.config.max_position_embeddings

        def pad(t, leaf):
            arr = np.asarray(leaf)
            shape = tuple(int(d) for d in t.shape)
            if tuple(arr.shape) != shape:
                ax = _row_axis(shape, cap)
                full = np.zeros(shape, t.dtype)
                full[(slice(None),) * ax + (slice(0, n),)] = arr
                arr = full
            return jnp.asarray(arr.astype(t.dtype, copy=False))

        return jax.tree_util.tree_map(pad, self._row_template, cache)

    def prefill_detached(
        self,
        prompt_ids,
        max_new_tokens: int = 32,
        *,
        uid_key: int = 0,
        prefix_id: Optional[int] = None,
        trace: Optional[int] = None,
    ) -> dict:
        """Run ONE request's prefill on THIS engine and return a
        host-transferable KV handoff instead of admitting it — the
        prefill half of disaggregated serving
        (:mod:`accelerate_tpu.serving_fleet`). The handoff carries the
        full prompt, the trimmed-to-``total``-rows KV cache as numpy
        leaves, the sampled first token + its logprob, and the advanced
        sampling-key data, so :meth:`submit_prefilled` on ANOTHER replica
        continues token- and logprob-exactly where a local prefill would
        have. ``wire_bytes`` is the payload a router accounts after the
        move; it equals ``price_kv_handoff``'s prediction exactly.

        ``uid_key`` seeds the per-request sampling chain (use the fleet
        uid: the stream is then deterministic per ``(seed, uid_key)``).
        With ``prefix_id``, ``prompt_ids`` is still the FULL prompt; its
        head must equal the registered prefix, whose cache seeds the
        chunk windows (radix-cache reuse composes with disaggregation on
        the prefill side)."""
        jax = _jax()
        self._check_handoff()
        prompt = np.asarray(prompt_ids, np.int32).ravel()
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        plen, pre = 0, None
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise ValueError(f"unknown prefix_id {prefix_id}; call register_prefix first")
            pre = self._prefixes[prefix_id]
            plen = pre["len"]
            if len(prompt) < plen + 1 or not np.array_equal(prompt[:plen], pre["tokens"]):
                raise ValueError("prompt does not start with the registered prefix tokens")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the slot cache ({self.max_len})"
            )
        next_tok, lp, cache, key = self._chunked_prefill(
            prompt, row_cache=None if pre is None else pre["cache"], done_upto=plen,
            key=self._request_key(int(uid_key)), trace=trace,
        )
        total = len(prompt)
        trimmed = self._trim_row_cache(cache, total)
        wire = int(sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(trimmed)))
        return {
            "prompt": prompt,
            "total": total,
            "max_new_tokens": int(max_new_tokens),
            "next_tok": int(next_tok),
            "lp": float(lp),
            "key_data": np.asarray(jax.random.key_data(key)),
            "cache": trimmed,
            "wire_bytes": wire,
            "reused_prefix_tokens": int(plen),
            "trace": trace,
        }

    def submit_prefilled(self, handoff: dict, stop_sequences=None, priority: int = 0) -> int:
        """Queue a request whose prefill already ran on another replica
        (:meth:`prefill_detached`): admission pastes the handed-off KV
        rows and emits the carried first token — ZERO prefill compute and
        zero tick token budget on this engine. Same shed/priority
        semantics as :meth:`submit`; outputs (tokens AND logprobs) are
        exact vs a local prefill by construction. A later preemption
        resumes by ordinary prefix recompute — the handoff payload is
        consumed at first admission."""
        if self._ring is not None:
            self._check_handoff()
        prompt = np.asarray(handoff["prompt"], np.int32).ravel()
        total, max_new = int(handoff["total"]), int(handoff["max_new_tokens"])
        if total != len(prompt):
            raise ValueError(f"handoff total {total} != prompt length {len(prompt)}")
        stops = tuple(tuple(int(t) for t in s) for s in (stop_sequences or ()))
        if any(len(s) == 0 for s in stops):
            raise ValueError("empty stop sequence")
        if total + max_new > self.max_len:
            raise ValueError(
                f"prompt ({total}) + max_new_tokens ({max_new}) "
                f"exceeds the slot cache ({self.max_len})"
            )
        if self.paged:
            need = self._new_blocks_for(0, total, max_new)
            if need > self._pcfg.num_blocks - 1:
                raise ValueError(
                    f"request needs {need} pool blocks but the pool has "
                    f"{self._pcfg.num_blocks - 1}; raise pool_blocks or paged_block_size"
                )
        trace = handoff.get("trace")
        with phase("engine.submit", uid=self._uid, prompt_tokens=len(prompt), queue_len=len(self.queue)):
            priority = self._admission_shed_check(int(priority), trace=trace)
            uid = self._uid
            self._uid += 1
            if self.tracer is not None and trace is not None:
                self.tracer.attach(trace, decode_uid=uid)
            req = _Request(
                uid, prompt, max_new, [], None, stops,
                priority=priority, submit_ts=time.monotonic(), handoff=dict(handoff),
                trace=trace,
            )
            self._queue_push(req)
            self._index[uid] = ("queued", req)
            self.metrics.on_submit(uid)
            return uid

    # ---- fleet failover: in-flight export / import (serving_fleet) ------

    def _snapshot_request(self, req: _Request) -> dict:
        """Portable base snapshot of one request: the FULL prompt (a
        registered prefix is inlined — the destination replica may not
        have it), the generated-so-far tokens/logprobs, and the admission
        metadata. The caller adds the sampling-chain ``key_data`` (which
        depends on where the request currently lives)."""
        prompt = req.prompt
        if req.prefix_id is not None:
            pre = self._prefixes[req.prefix_id]
            prompt = np.concatenate([np.asarray(pre["tokens"], np.int32), prompt])
        return {
            "uid": int(req.uid),
            "prompt": np.asarray(prompt, np.int32),
            "max_new_tokens": int(req.max_new_tokens),
            "out_tokens": [int(t) for t in req.out_tokens],
            "out_lps": [float(v) for v in req.out_lps],
            "stop_sequences": req.stop_sequences,
            "priority": int(req.priority),
            "trace": req.trace,
        }

    def export_inflight(self, include_kv: bool = True) -> list:
        """Snapshot EVERY in-flight request (queued + active) for
        migration to another replica — the failover half of
        :mod:`accelerate_tpu.serving_fleet`. Non-mutating: the engine is
        left exactly as found, but for retirements' clears that had not
        reached the device yet, which are sent first (the router decides
        what to do with the husk). Each snapshot carries the request plus
        its sampling-chain ``key_data``, so :meth:`import_inflight` on a survivor continues
        token- and logprob-exactly; decoding slots additionally export
        their trimmed KV rows (``cache`` + ``rows``) when ``include_kv``
        and the layout allows (dense — paged slots fail over by prefix
        recompute, which is equally exact).

        Safe at every labeled serving crash point by construction: the
        crash hooks fire BEFORE the jitted tick calls, so the host
        bookkeeping (out_tokens, slot_pos, slot keys, unconsumed
        handoffs) is always consistent when a failover export runs, and a
        tick that one of them cuts short reads the first tokens it had
        left on the device before it raises (:meth:`_tick_phases`)."""
        jax = _jax()
        if self._ring is not None:
            raise NotImplementedError(self._ring_refusal(
                "export_inflight (a migrated request resumes through chunk windows, or by its K/V rows)"))
        self._flush_clears()
        kv_ok = include_kv and not self.paged
        if kv_ok:
            check_no_state_leaf(self._row_template, "export_inflight(include_kv=True)")
            check_no_summary_leaf(self._row_template, "export_inflight(include_kv=True)")
        snaps = []

        def handoff_snap(req, h):
            # an unconsumed handoff payload (queued or awaiting paste):
            # fold its sampled first token into the output stream — the
            # importer re-feeds it at the pasted frontier (or recomputes)
            snap = self._snapshot_request(req)
            if h["next_tok"] is not None:
                snap["out_tokens"] = snap["out_tokens"] + [int(h["next_tok"])]
                snap["out_lps"] = snap["out_lps"] + [float(h["lp"])]
            snap["key_data"] = np.asarray(h["key_data"])
            if kv_ok and h.get("cache") is not None:
                snap["cache"], snap["rows"] = h["cache"], int(h["total"])
            return snap

        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            if self.slot_phase[slot] == "decode":
                snap = self._snapshot_request(req)
                snap["key_data"] = np.asarray(jax.random.key_data(self._slot_keys[slot]))
                if kv_ok:
                    rows = int(self.slot_pos[slot])
                    row = jax.tree_util.tree_map(lambda big: big[slot], self.slot_caches)
                    snap["cache"], snap["rows"] = self._trim_row_cache(row, rows), rows
                snaps.append(snap)
                continue
            st = self._prefill_state[slot]
            if st is not None and st.get("handoff") is not None:
                snaps.append(handoff_snap(req, st["handoff"]))
                continue
            snap = self._snapshot_request(req)
            key = self._chain_key(*(st["key"] if st is not None else self._request_key(req.uid)))
            snap["key_data"] = np.asarray(jax.random.key_data(key))
            snaps.append(snap)
        for req in self.queue:
            if req.handoff is not None:
                snaps.append(handoff_snap(req, req.handoff))
                continue
            snap = self._snapshot_request(req)
            key = self._chain_key(*self._request_key(req.uid, req.resume_key))
            snap["key_data"] = np.asarray(jax.random.key_data(key))
            snaps.append(snap)
        return snaps

    def import_inflight(self, snap: dict) -> int:
        """Admit a migrated request exported by another replica's
        :meth:`export_inflight`, continuing its stream token- and
        logprob-exactly: the carried ``key_data`` pins the sampling chain
        and the resume machinery re-feeds the last generated token at the
        recomputed (or KV-pasted, when ``cache`` shipped) frontier.
        Bypasses the submit-time shed gate — migrated work already passed
        admission once; shedding it now would LOSE it. Returns this
        engine's local uid for the request."""
        jax = _jax()
        prompt = np.asarray(snap["prompt"], np.int32).ravel()
        out = [int(t) for t in snap.get("out_tokens") or []]
        lps = [float(v) for v in snap.get("out_lps") or []]
        max_new = int(snap["max_new_tokens"])
        if len(prompt) == 0:
            raise ValueError("empty prompt in failover snapshot")
        if len(lps) != len(out):
            raise ValueError(f"snapshot logprobs ({len(lps)}) misaligned with tokens ({len(out)})")
        if len(out) > max_new:
            raise ValueError(f"snapshot carries {len(out)} tokens > max_new_tokens {max_new}")
        if self._aligned is not None and out:
            raise NotImplementedError(self._aligned_refusal("import_inflight of a request that has decoded (it resumes through chunk windows)"))
        if self._ring is not None and (out or snap.get("cache") is not None):
            raise NotImplementedError(self._ring_refusal("import_inflight of a request that has decoded (it resumes through chunk windows, or by its K/V rows)"))
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds the slot cache ({self.max_len})"
            )
        cache, rows = snap.get("cache"), int(snap.get("rows") or 0)
        if cache is not None:
            if not out:
                raise ValueError("KV failover import needs a generated token to re-feed")
            if rows != len(prompt) + len(out) - 1:
                raise ValueError(
                    f"KV rows ({rows}) != prompt ({len(prompt)}) + "
                    f"generated ({len(out)}) - 1 — not a consistent decode frontier"
                )
        stops = tuple(tuple(int(t) for t in s) for s in (snap.get("stop_sequences") or ()))
        uid = self._uid
        self._uid += 1
        req = _Request(
            uid, prompt, max_new, out, None, stops,
            out_lps=lps, priority=int(snap.get("priority", 0)),
            submit_ts=time.monotonic(), preempted=bool(out), ttft_done=bool(out),
            resume_key=jax.random.wrap_key_data(jax.numpy.asarray(snap["key_data"])),
            trace=snap.get("trace"),
        )
        if cache is not None:
            req.handoff = {
                "cache": cache, "total": rows, "next_tok": None, "lp": None,
                "key_data": np.asarray(snap["key_data"]),
            }
        self._queue_push(req)
        self._index[uid] = ("queued", req)
        self.metrics.on_submit(uid)
        self.metrics.on_failover_in()
        self._log.event(
            "failover_in", uid=uid, source_uid=int(snap.get("uid", -1)),
            generated=len(out), kv_rows=rows if cache is not None else 0,
            trace=snap.get("trace"),
        )
        return uid

    def _admission_shed_check(self, priority: int, trace: Optional[int] = None) -> int:
        """Shared submit-time SLO gate (:meth:`submit` /
        :meth:`submit_prefilled`): returns the possibly-demoted priority,
        or raises the structured :class:`ShedError` rejection. A shed
        rejection closes the request's trace (status ``shed``) — the
        trace id rides the shed event and the raised error."""
        reason = self._sched.shed_on_submit(priority, len(self.queue))
        if reason is None:
            return priority
        cfg = self._sched.config
        if cfg.shed_action == "deprioritize":
            self.metrics.on_deprioritize(None)
            self._log.event(
                "shed", action="deprioritize", priority=priority,
                queue_depth=len(self.queue), reason=reason, trace=trace,
            )
            return max(priority, cfg.deprioritize_to)
        self.metrics.on_shed(None)
        self._log.event(
            "shed", action="reject", priority=priority,
            queue_depth=len(self.queue), reason=reason, trace=trace,
        )
        if self.tracer is not None and trace is not None:
            self.tracer.finish(trace, status="shed", reason=reason)
        raise ShedError(reason, priority=priority, queue_depth=len(self.queue), trace_id=trace)

    def _queue_push(self, req: _Request) -> None:
        """Insert by the scheduler's order key (priority class, then
        submission order) — a preempted request's original uid keeps its
        place ahead of later arrivals in the same class."""
        bisect.insort(self.queue, req, key=lambda r: self._sched.order_key(r.priority, r.uid))

    def poll(self, uid: int):
        """The finished [S + new] tokens for ``uid``, or None if pending.
        Raises the request's structured :class:`ShedError` if the
        scheduler shed it from the queue (SLO load shedding)."""
        if uid in self._shed:
            raise self._shed[uid]
        return self.done.get(uid)

    def _locate(self, uid: int):
        """``("done"|"active"|"queued", req)`` for a known id (``req`` is
        None once done); raises KeyError for unknown/cancelled ids and the
        stored ShedError for shed ids. O(1): admit/retire/cancel/preempt
        maintain the uid index — streaming accessors never scan slots or
        the queue, so ``poll``/``partial`` stay flat under thousands of
        queued requests."""
        if uid in self._shed:
            raise self._shed[uid]
        try:
            return self._index[uid]
        except KeyError:
            raise KeyError(f"unknown request id {uid}") from None

    def partial(self, uid: int) -> np.ndarray:
        """Tokens generated SO FAR for ``uid`` (streaming surface) —
        ALWAYS the generated suffix (empty while queued), including after
        completion, so a delta-by-length streamer never re-emits prompt
        tokens; ``poll`` returns the full prompt+output sequence. Raises
        KeyError for unknown (or cancelled) ids. A preempted-and-requeued
        request keeps exposing its already-streamed tokens while it waits
        to resume — a delta streamer sees no regression across the
        eviction."""
        state, req = self._locate(uid)
        if state == "done":
            return self._done_new[uid]
        return np.asarray(req.out_tokens, np.int32)

    def logprobs(self, uid: int) -> np.ndarray:
        """log P(token) for each GENERATED token so far, under the model's
        full next-token distribution (f32 log-softmax — the standard
        serving logprob surface even when sampling is temperature/top-k
        shaped). Aligned with :meth:`partial` while decoding and with
        :meth:`poll`'s generated suffix once finished; empty while queued.
        Raises KeyError for unknown (or cancelled) ids."""
        state, req = self._locate(uid)
        if state == "done":
            return self._done_lps[uid]
        return np.asarray(req.out_lps, np.float32)

    def cancel(self, uid: int) -> np.ndarray:
        """Abort a queued, prefilling, or decoding request, returning
        whatever tokens it had generated (a preempted-and-requeued request
        returns its carried tokens). Its slot/pool blocks free
        immediately; ``poll`` never resolves a cancelled id. Raises
        ValueError if already finished, KeyError if unknown or shed."""
        if uid in self.done:
            raise ValueError(f"request {uid} already finished; poll() it instead")
        state, req = self._index.get(uid, (None, None))
        if state == "active":
            slot = next(s for s, r in enumerate(self.slot_req) if r is req)
            out = np.asarray(req.out_tokens, np.int32)
            self._release(slot)
            del self._index[uid]
            self.metrics.on_cancel(uid)
            if self.tracer is not None:
                self.tracer.finish(req.trace, status="cancelled")
            return out
        if state == "queued":
            self.queue.remove(req)
            del self._index[uid]
            self.metrics.on_cancel(uid)
            if self.tracer is not None:
                self.tracer.finish(req.trace, status="cancelled")
            return np.asarray(req.out_tokens, np.int32)
        raise KeyError(f"unknown request id {uid}")

    @property
    def active_count(self) -> int:
        return sum(r is not None for r in self.slot_req)

    def step(self) -> int:
        """One engine tick under the token-budget continuous-batching
        scheduler: shed over-SLO queue entries, advance in-flight prefill
        chunks and admissions inside the tick's remaining token budget
        (active decodes claim ``n_decoding x tick_block`` first), then
        ONE decode tick for every decoding slot. Returns the number of
        occupied slots after the tick.

        The tick's programs are queued on the device back to back: every
        admission's prefill and paste, then the decode tick, with no host
        wait between them. A fresh admission's first token stays on the
        device, is fed to the decode tick there, and the host reads it
        (``engine.prefill.sync``, the TTFT instant) only once the decode
        tick is dispatched; then it waits for the tick's tokens and walks
        them. So the host's admission and dispatch work runs under the
        prefill before it. No first token is pending when ``step()``
        returns (nor when it raises): ``partial``, ``poll``, ``cancel``
        and ``export_inflight`` between steps find every token a prefill
        sampled.

        With the default config (unlimited budget) every admitted prefill
        completes in its admission tick — the pre-scheduler behavior.
        With a budget, a long prompt streams one chunk window per tick
        while decodes keep ticking: new requests make TTFT progress
        without ever stalling running decodes. The engine always forces
        at least one unit of progress per tick, so no budget setting can
        livelock ``run()``."""
        crash_point("pre_tick", replica=self.metrics.replica)
        n_dec = sum(1 for ph in self.slot_phase if ph == "decode")
        self._tick += 1
        with phase(
            "engine.tick", tick=self._tick, queue_len=len(self.queue), decoding=n_dec,
            prefilling=len(self._prefill_order),
        ) as tick:  # a root: phase() adds its ``mono_ns``
            self._tick_phases(n_dec)
        self.metrics.on_tick(tick.record.wall_ns / 1e6, tick.record.slow)
        if tick.record.slow:
            self._report_slow_tick(tick.record)
        return self.active_count

    def _report_slow_tick(self, record) -> None:
        """A tick that closed far beyond the median of the ticks before it
        (``telemetry.trace.SLOW_ROOT_OVER_NS``): one ``tick_slow`` event with
        the tick's whole record, which the flight recorder's tap keeps, and
        one warning line, which a run with no event log still prints."""
        fields = record.fields()
        self._log.event("tick_slow", severity="warning", **fields)
        logger.warning(
            "tick_slow: tick %s took %.1f ms (cpu %.1f ms, gap before it %.1f ms), longest child %s; "
            "children_ms %s; counts %s; done %s",
            fields["counts"].get("tick"), fields["wall_ms"], fields["cpu_ms"], fields["gap_ms"],
            fields["longest_child"], fields["children_ms"], fields["counts"], fields["done"],
        )

    def _tick_phases(self, n_dec: int) -> None:
        """The body of one tick, cut into the non-overlapping phases
        :data:`~accelerate_tpu.telemetry.trace.PHASES` lists: what a
        profile shows inside ``engine.tick``."""
        m = self.metrics
        admitted, tokens_was, completed_was = 0, m.tokens_generated, m.requests_completed
        self._tick_prefill_tokens = self._tick_head_rows = self._tick_first_deferred = self._tick_clears_deferred = 0
        leaves_signed_was = self._pc.leaves_signed
        self._tick_expert_load = (0, 0, 0, 0)
        self._tick_state_idle = 0
        self._tick_rows_skipped = 0
        self._tick_windows = (0, 0, 0, 0)
        self._tick_window_rows = 0
        with phase("engine.schedule"):
            now = time.monotonic()
            self._pool_blocked = False
            self._shed_pass(now)
            budget = self._sched.tick_budget(n_dec, self.tick_block)
        # Admissions run FIRST and one admission per tick may overrun the
        # budget: a queued request's TTFT progress must not wait for an
        # in-flight long prefill to finish streaming (head-of-line
        # blocking is exactly what this scheduler removes). In-flight
        # prefills then take the leftover budget oldest-first, with a
        # one-window anti-starvation guarantee so a long prompt finishes
        # in at most windows-many ticks under sustained arrivals. Decodes
        # tick every step regardless — the per-tick prefill stall is
        # bounded by budget + two forced windows, never a whole prompt.
        try:
            force = True
            while self.queue:
                if budget <= 0 and not force:
                    break
                slot = next((s for s in range(self.num_slots) if self.slot_req[s] is None), None)
                if slot is None:
                    # priority inversion: a strictly more important request
                    # waits while a lower class decodes — evict the youngest
                    # such decode (policy-gated; None without preemption)
                    with phase("engine.schedule"):
                        slot = self._sched.pick_victim(self.queue[0].priority, self._decoding_info())
                        if slot is not None:
                            self._preempt(slot)
                    if slot is None:
                        break
                head = self.queue[0]
                with phase(
                    "engine.admit", uid=head.uid, slot=slot, prompt_tokens=len(head.prompt),
                    queue_wait_ms=(time.monotonic() - head.submit_ts) * 1000.0, **self._blocks_by_kind(head),
                ):
                    if not self._admit(slot):
                        break  # pool blocked: the whole queue waits on its head
                admitted += 1
                budget = self._advance_prefill(slot, budget, force=force)
                force = False
            force = True
            for slot in list(self._prefill_order):
                budget = self._advance_prefill(slot, budget, force=force)
                force = False
            if any(ph == "decode" for ph in self.slot_phase):
                self._decode_pass()
        finally:
            # no pending first token outlives step(): the decode pass has read them all behind its dispatch,
            # and a tick that a crash point cut short reads here what it had admitted, so that whoever
            # exports this engine's requests finds every first token a prefill sampled
            self._read_first_tokens()
        with phase("engine.expire"):
            self._expire_window_blocks()
            if self.active_count == 0:
                self._flush_clears()  # nothing is left to send them behind: an idle engine holds no stale row
        leaves_signed = self._pc.leaves_signed - leaves_signed_was
        m.on_host_work(self._tick_clears_deferred, leaves_signed, self._tick_head_rows)
        pages = (0, 0)
        if self._aligned is not None:
            self._close_windows()
            pages = (sum(map(len, self._slot_blocks)), sum(map(len, self._slot_summary)))
            m.on_pages_held(*pages)
        by_kind = (0, 0)
        if self._ring is not None:
            by_kind = (sum(map(len, self._slot_blocks)), sum(map(len, self._slot_ring)))
            m.on_pages_by_kind(*by_kind)
        with phase(
            "engine.tick.done", admitted=admitted, first_tokens_deferred=self._tick_first_deferred,
            clears_deferred=self._tick_clears_deferred, leaves_signed=leaves_signed,
            prefill_tokens=self._tick_prefill_tokens, head_rows=self._tick_head_rows,
            emitted=m.tokens_generated - tokens_was, retired=m.requests_completed - completed_was,
            pool_blocked=int(self._pool_blocked), free_blocks=self.pool_free_blocks if self.paged else -1,
            queue_len=len(self.queue), experts_touched=self._tick_expert_load[0],
            expert_pairs_max=self._tick_expert_load[1], expert_tile_visits=self._tick_expert_load[2],
            expert_pairs=self._tick_expert_load[3], state_slots_idle=self._tick_state_idle,
            attention_rows_skipped=self._tick_rows_skipped,
            attn_rows_read=self._tick_windows[0], context_rows=self._tick_windows[1], chunks_pooled=self._tick_windows[2],
            windows_closed=self._tick_windows[3], exact_pages=pages[0], summary_pages=pages[1],
            window_rows_read=self._tick_window_rows, full_pages=by_kind[0], window_pages=by_kind[1],
        ):
            pass

    # ---- scheduler passes (one step() = one tick) -----------------------

    def _decoding_info(self) -> list:
        """``[(slot, priority, uid), ...]`` for decode-phase slots — the
        scheduler's victim-selection view."""
        return [
            (slot, req.priority, req.uid)
            for slot, req in enumerate(self.slot_req)
            if req is not None and self.slot_phase[slot] == "decode"
        ]

    def _shed_pass(self, now: float) -> None:
        """SLO queue-wait enforcement: sheddable requests whose wait has
        blown ``max_queue_wait_s`` are rejected with a structured
        :class:`ShedError` (surfaced by the next ``poll``) or demoted
        once (``shed_action="deprioritize"``) — never silently queued."""
        cfg = self._sched.config
        if cfg.max_queue_wait_s is None or not self.queue:
            return
        for req in list(self.queue):
            wait_s = now - req.submit_ts
            reason = self._sched.shed_on_wait(req.priority, wait_s)
            if reason is None:
                continue
            if cfg.shed_action == "deprioritize":
                if req.deprioritized or req.priority >= cfg.deprioritize_to:
                    continue
                self.queue.remove(req)
                req.deprioritized = True
                req.priority = cfg.deprioritize_to
                self._queue_push(req)
                self.metrics.on_deprioritize(req.uid)
                self._log.event(
                    "shed", action="deprioritize", uid=req.uid, priority=req.priority,
                    queue_wait_ms=round(wait_s * 1000.0, 3), reason=reason,
                )
            else:
                self.queue.remove(req)
                err = ShedError(
                    reason, uid=req.uid, priority=req.priority,
                    queue_depth=len(self.queue), queue_wait_ms=wait_s * 1000.0,
                    trace_id=req.trace,
                )
                self._shed[req.uid] = err
                self._index.pop(req.uid, None)
                self.metrics.on_shed(req.uid)
                self._log.event(
                    "shed", action="reject", uid=req.uid, priority=req.priority,
                    queue_wait_ms=round(wait_s * 1000.0, 3), reason=reason, trace=req.trace,
                )
                if self.tracer is not None:
                    self.tracer.finish(req.trace, status="shed", reason=reason)

    def _reserve_blocks(self, req: _Request):
        """Reserve the paged pool blocks a request needs (resume-aware);
        ``(owned, shared_entries, table, write_row)`` or None when the
        pool cannot satisfy it."""
        plen, prompt_len, max_new = self._request_block_dims(req)
        if self._aligned is not None:
            return self._reserve_aligned(plen + prompt_len, max_new)
        lo, hi, alias_hi = self._plan_blocks(plen, prompt_len, max_new)
        shared_entries: dict[int, int] = {}
        if req.prefix_id is not None:
            pids = self._prefixes[req.prefix_id]["block_ids"]
            # every i in [lo, alias_hi) is registered: the prefix's
            # lo_min (suffix length 1) lower-bounds any request's lo
            shared_entries = {i: pids[i] for i in range(lo, alias_hi)}
        new_ids = self._alloc.alloc((hi - lo) - len(shared_entries))
        if new_ids is None:
            return None
        if self._ring is not None and not self._reserve_ring(plen + prompt_len, max_new):
            self._alloc.free(new_ids)  # the window layers' pool is the scarcer: nothing is held while the request waits
            return None
        for bid in shared_entries.values():
            self._shared_refs[bid] += 1
        table = np.zeros((self._mb,), np.int32)  # pad/out-of-band -> trash sink
        owned: dict[int, int] = {}
        ids = iter(new_ids)
        for i in range(lo, hi):
            if i in shared_entries:
                table[i] = shared_entries[i]
            else:
                owned[i] = table[i] = next(ids)
        # the paste writes ONLY this request's own blocks: shared prefix
        # entries go to the trash sink in the write row (their canonical
        # content was written at registration)
        write_row = table.copy()
        for i in shared_entries:
            write_row[i] = 0
        return owned, shared_entries, table, write_row

    def _request_key(self, uid: int, carried=None) -> tuple:
        """``(key, fold)`` as the programs that sample a request's first token take them
        (``serving_programs.request_key``): the engine's key and the uid for a fresh request, whose chain
        ``fold_in(key(seed), uid)`` the program then derives itself, so that an admission runs no eager program
        ahead of its prefill; a chain ``carried`` in (a resume, a hand-off, a migrated request) with nothing to fold."""
        if carried is not None:
            return carried, _NO_FOLD
        if 0 <= uid <= np.iinfo(np.int32).max:
            return self._base_key, np.int32(uid)
        return _jax().random.fold_in(self._base_key, uid), _NO_FOLD  # a uid the program's int32 does not hold

    @staticmethod
    def _chain_key(key, fold):
        """The chain a :meth:`_request_key` pair stands for, computed here: for whoever needs the key itself."""
        return key if fold < 0 else _jax().random.fold_in(key, int(fold))

    def _admit(self, slot: int) -> bool:
        """Move the queue head into ``slot`` in the prefill phase,
        reserving its pool blocks first (paged). Under pool exhaustion,
        policy may evict the youngest lower-priority decode and retry
        once; failing that, admission blocks (returns False) and the
        whole queue waits on its head — no starvation of large requests
        by later small ones."""
        jax = _jax()
        req = self.queue[0]
        if self.paged:
            plan = self._reserve_blocks(req)
            if plan is None:
                victim = self._sched.pick_victim(req.priority, self._decoding_info())
                if victim is not None:
                    self._preempt(victim)
                    plan = self._reserve_blocks(req)
            if plan is None:
                self._pool_blocked = True
                self.metrics.on_pool_blocked()
                return False
            owned, shared_entries, table, write_row = plan
        self.queue.pop(0)
        resume = req.preempted and len(req.out_tokens) > 0
        st: dict = {"req": req, "resume": resume, "bucket": None}
        if self.paged:
            self._slot_blocks[slot], self._slot_shared[slot] = owned, shared_entries
            self._slot_table[slot] = table
            st["table"], st["write_row"] = table, write_row
            if self._aligned is not None:
                self._slot_summary[slot], st["summary_row"], self._slot_last[slot] = self._reserved_summary
            if self._ring is not None:
                self._slot_ring[slot], st["window_row"] = self._reserved_ring
        # the per-request sampling chain: the uid folded into the engine's key at first admission (by the
        # program that samples the first token: no eager program runs here), the evicted chain carried across a
        # preemption — the resumed stream continues the SAME chain, so sampled outputs stay request-exact
        st["key"] = self._request_key(req.uid, req.resume_key)
        if req.handoff is not None:
            # disaggregated admission: the KV rows, first token, and the
            # advanced sampling chain all arrived with the handoff — no
            # prefill program runs here. Consumed once: a preemption
            # resumes by the ordinary recompute path below. A FAILOVER
            # import (export_inflight -> import_inflight) rides the same
            # path with resume=True: the pasted rows are the migrated
            # request's exact KV frontier, and the resume finalize re-feeds
            # its carried last token instead of emitting h["next_tok"].
            st["handoff"] = req.handoff
            st["key"] = self._request_key(
                req.uid, jax.random.wrap_key_data(jax.numpy.asarray(req.handoff["key_data"]))
            )
            req.handoff = None
        elif not resume and req.prefix_id is None and (b := self._bucket_for(len(req.prompt))) is not None:
            # short prompt, no prefix: the one-shot fused program
            st["bucket"] = b
        else:
            # prefix-seeded, long, or resumed prompt: chunk windows. The
            # stored prefix cache is never mutated — jax arrays are
            # immutable, each request builds on its own copy. A resumed
            # request recomputes prompt + all-but-last generated tokens;
            # its last token is re-fed at the recomputed frontier.
            pre = self._prefixes[req.prefix_id] if req.prefix_id is not None else None
            parts = ([] if pre is None else [pre["tokens"]]) + [req.prompt]
            if resume:
                parts.append(np.asarray(req.out_tokens[:-1], np.int32))
            st["full"] = parts[0] if len(parts) == 1 else np.concatenate(parts)
            st["done"] = 0 if pre is None else pre["len"]
            st["cache"] = None if pre is None else pre["cache"]
            st["logits"], st["s_last"] = None, 0
        self.slot_req[slot] = req
        self.slot_phase[slot] = "prefill"
        self._prefill_state[slot] = st
        self._prefill_order.append(slot)
        self._index[req.uid] = ("active", req)
        wait_ms = (time.monotonic() - req.submit_ts) * 1000.0
        self.metrics.on_admit(req.uid, priority=req.priority, queue_wait_ms=wait_ms)
        if not resume:
            self._log.event(
                "admit", uid=req.uid, priority=req.priority, queue_wait_ms=round(wait_ms, 3)
            )
        if self.tracer is not None:
            # queue_wait absorbs everything since the frontier (for a
            # fresh submit: since the trace started); accounted_ms is the
            # scheduler's own number — critpath cross-checks the two
            self.tracer.seg(req.trace, "queue_wait", accounted_ms=round(wait_ms, 3))
            self.tracer.seg(req.trace, "admit", resume=resume)
        return True

    def _advance_prefill(self, slot: int, budget: float, force: bool = False) -> float:
        """Spend tick budget on one slot's prefill: whole fused-bucket
        programs or chunk windows, each claiming its width in tokens.
        ``force`` lets the first window run even over budget (admission
        TTFT progress / anti-starvation — also why no budget setting can
        livelock ``run()``); an unaffordable later window waits for the
        next tick's budget."""
        jnp = _jax().numpy
        st = self._prefill_state[slot]
        if st is None:
            return budget
        crash_point("mid_prefill", replica=self.metrics.replica)
        req = st["req"]
        if st.get("handoff") is not None:
            # the prefill compute already happened on another replica:
            # pad the trimmed rows back onto the template and paste —
            # zero tokens of this tick's budget are spent
            h = st.pop("handoff")
            self._flush_clears()  # no program of this admission stands ahead of its paste: today's order
            with phase("engine.prefill.dispatch", uid=req.uid, tokens=0, prompt_tokens=len(req.prompt)):
                cache = self._untrim_row_cache(h["cache"], h["total"])
            if self.tracer is not None:
                # the paste half of the handoff (the router recorded the
                # priced wire move); no moved_bytes here, so critpath
                # skips this span's byte check by design
                self.tracer.seg(req.trace, "kv_handoff", phase="paste", rows=int(h["total"]))
            self._finalize_prefill(slot, cache, h["total"], h["next_tok"], h["lp"], st["key"][0])
            return budget
        if st["bucket"] is not None:
            b = st["bucket"]
            if budget < b and not force:
                return budget
            if len(self._first_pending) >= self._row_cache_cap():
                # as many row caches as memory has room for already wait on the device for their pastes (each is
                # freed only when its paste has run): let the device finish them before another is asked for
                with phase("engine.prefill.room.sync", uid=req.uid, waiting=len(self._first_pending)):
                    _jax().block_until_ready(_jax().tree_util.tree_leaves(self.slot_caches)[0])
            with phase("engine.prefill.dispatch", uid=req.uid, tokens=b, prompt_tokens=len(req.prompt)):
                padded = np.zeros((1, b), np.int32)
                padded[0, : len(req.prompt)] = req.prompt
                # the request's ``prefill`` span closes at the first-token
                # sync in _finalize_prefill: dispatch to there is compute
                st["dispatched"] = (time.perf_counter(), int(b))
                # the ids and the length go to the executable as they are: it puts them on the device itself
                next_tok, lp, row_cache, key = self._prefill[b](
                    self.model.params, padded, np.int32(len(req.prompt)), *st["key"]
                )
            self._tick_prefill_tokens += b
            self._tick_head_rows += 1  # the bucket's program heads the one row it keeps
            self._finalize_prefill(slot, row_cache, len(req.prompt), next_tok, lp, key)
            return budget - b
        full = st["full"]
        t = len(full)
        while st["done"] < t:
            w, _, _ = self._next_window(t, st["done"])
            if budget < w and not force:
                return budget
            with phase("engine.prefill.dispatch", uid=req.uid, tokens=w, prompt_tokens=len(req.prompt)):
                st["logits"], st["cache"], st["s_last"], st["done"] = self._run_window(
                    full, st["done"], st["cache"], trace=req.trace
                )
            self._tick_prefill_tokens += w
            self._tick_head_rows += w  # a chunk window's logits come back whole (``_sample_at`` picks one)
            budget -= w
            force = False
        next_tok = lp = None
        key = st["key"][0]  # a resume's is the chain it carried
        with phase("engine.prefill.dispatch", uid=req.uid, tokens=0, prompt_tokens=len(req.prompt)):
            cache = self._reset_idx(st["cache"], jnp.int32(t))
            if not st["resume"]:
                next_tok, lp, key = self._sample_at(st["logits"], jnp.int32(t - 1 - st["s_last"]), *st["key"])
        self._finalize_prefill(slot, cache, t, next_tok, lp, key)
        return budget

    def _row_cache_cap(self):
        """How many admissions of one tick may have their prefill's row cache on the device at once. The tick's
        programs are queued back to back and a row cache is freed only when its paste has run, so a tick that
        admits many holds as many row caches as it has queued: half of the device memory that is free once the
        engine stands (weights and cache resident) over a row cache's bytes, at least one. Most models' row caches
        are small beside it (tens of MB) and the cap is never met; an EVA model's is 84 KB a position a layer.
        Unbounded where the backend reports no memory (a CPU)."""
        if self._row_cap is None:
            jax = _jax()
            stats = jax.local_devices()[0].memory_stats() or {}
            row = sum(math.prod(l.shape) * np.dtype(l.dtype).itemsize for l in jax.tree_util.tree_leaves(self._row_template))
            free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
            self._row_cap = max(1, free // 2 // max(row, 1)) if stats.get("bytes_limit") else math.inf
        return self._row_cap

    def _finalize_prefill(self, slot: int, row_cache, total: int, next_tok, lp, key) -> None:
        """Prefill complete: paste/insert the row cache and move the slot
        to the decode phase. A resume re-feeds the carried last token at
        the recomputed frontier without sampling anything; a hand-off's
        first token came on the host. A fresh admission's is still on the
        device: it is noted as pending, nothing here waits for it, and the
        tick's decode pass feeds it on the device and reads it behind its
        own dispatch (:meth:`_read_first_token`: TTFT). Only a request that
        can take one token, which no decode pass will advance, is read here."""
        st = self._prefill_state[slot]
        req = st["req"]
        with phase("engine.prefill.paste", uid=req.uid):
            # the retirements' clears, behind the prefill that was just dispatched and ahead of the paste
            self._flush_clears(deferred=True)
            if self.paged:
                # the second table's row, where the cache has one: an EVA layer's summary pages, a window layer's ring
                second = (st["summary_row"],) if self._aligned is not None else (st["window_row"],) if self._ring is not None else ()
                self.slot_caches, self._slot_keys = self._paste(
                    self.slot_caches, self._slot_keys, row_cache, key, st["write_row"], st["table"],
                    np.int32(slot), np.int32(total), *second,
                )
            else:
                self.slot_caches, self._slot_keys = self._insert(
                    self.slot_caches, self._slot_keys, row_cache, key, np.int32(slot)
                )
        self._prefill_state[slot] = None
        self._prefill_order.remove(slot)
        self.slot_phase[slot] = "decode"
        self.slot_pos[slot] = total
        if st["resume"]:
            # token- and logprob-exact by construction: nothing is
            # re-sampled; already-streamed tokens/logprobs are untouched
            self.slot_tok[slot] = int(req.out_tokens[-1])
            self.metrics.on_resume(req.uid)
            self._log.event(
                "resume", uid=req.uid, priority=req.priority,
                recomputed_tokens=int(total), generated=len(req.out_tokens),
            )
            if self.tracer is not None:
                self.tracer.seg(req.trace, "resume", recomputed_tokens=int(total))
            return
        self._first_pending[slot] = (next_tok, lp, req, st.get("dispatched"))  # not ``st``: it holds a chunk path's logits
        if not isinstance(next_tok, _jax().Array) or req.max_new_tokens == 1:
            self._read_first_token(slot)

    def _read_first_token(self, slot: int) -> None:
        """Bring a pending admission's first token to the host (the sync:
        TTFT) and record it; a request that it ends is retired, and
        whatever a dispatched decode pass computes for its slot reaches
        nobody (its blocks are freed behind that pass: the device runs its
        programs in order)."""
        next_tok, lp, req, dispatched = self._first_pending.pop(slot)
        with phase("engine.prefill.sync", uid=req.uid):
            tok, lp = int(next_tok), float(lp)  # the first token exists on the host from here
        if self.tracer is not None and dispatched is not None:
            t0, b = dispatched
            self.tracer.seg(
                req.trace, "prefill", tokens=b, compute_ms=round((time.perf_counter() - t0) * 1000.0, 3)
            )
        req.out_tokens.append(tok)
        req.out_lps.append(lp)
        if not req.ttft_done:
            req.ttft_done = True
            self.metrics.on_first_token(req.uid)  # TTFT: prefill's tail token
        self.metrics.on_tokens(1)
        if self._finished(req, tok):
            self._retire(slot)
            return
        self.slot_tok[slot] = tok

    def _read_first_tokens(self) -> None:
        """Every pending first token, in admission order."""
        for slot in list(self._first_pending):
            self._read_first_token(slot)

    def _preempt(self, slot: int) -> None:
        """Evict a decoding slot: requeue its request with the
        generated-so-far tokens and its sampling chain, free the slot and
        its KV blocks now. The resume admission rebuilds the cache by
        chunked recomputation — see :meth:`_finalize_prefill`. A slot
        admitted in this very tick has its first token read first: the
        request carries it away, or ends with it and needs no eviction."""
        if slot in self._first_pending:
            self._read_first_token(slot)
            if self.slot_req[slot] is None:
                return
        req = self.slot_req[slot]
        req.resume_key = self._slot_keys[slot]
        req.preempted = True
        self._release(slot)
        self._queue_push(req)
        self._index[req.uid] = ("queued", req)
        self.metrics.on_preempt_decode(req.uid)
        self._log.event(
            "preempt_decode", uid=req.uid, priority=req.priority,
            generated=len(req.out_tokens),
        )
        if self.tracer is not None:
            self.tracer.seg(req.trace, "preempt", generated=len(req.out_tokens))

    def _decode_pass(self) -> None:
        """ONE jitted K-step tick for every decode-phase slot, then the
        host walk that streams tokens/logprobs out. A slot admitted in
        this tick takes its first token from the prefill on the device
        (``feed_first_token``), and the host reads that token between the
        tick's dispatch and its sync, while the device works. Prefilling slots
        compute garbage rows by construction (static shapes) — their
        caches are fully replaced at prefill paste/insert. A tick that
        takes :meth:`_decoding_arg` spends no expert and no state-space
        step on them: a slot in which nobody decodes keeps its
        ``ssm_state`` bit for bit until the next paste replaces it."""
        crash_point("mid_decode", replica=self.metrics.replica)
        jnp = _jax().numpy
        decoding = self._decoding_slots()
        n_decoding = int(decoding.sum())
        with phase(
            "engine.decode.dispatch", decoding=n_decoding, tick_block=self.tick_block,
            live_tokens=int(self.slot_pos[decoding].sum()),
        ):
            self._flush_clears(deferred=True)  # a tick that admitted nothing: its clears ride ahead of the decode program
            toks = jnp.asarray(self.slot_tok)
            for slot, pending in self._first_pending.items():
                toks = self._feed_first_token(toks, jnp.int32(slot), pending[0])
            self.slot_caches, toks_k, lps_k, self._slot_keys, load_k = self._decode_tick(
                self.model.params, self.slot_caches,
                toks, jnp.asarray(self.slot_pos), self._slot_keys, *self._decoding_arg(decoding)
            )
        # the tick's programs are queued back to back; only now does the host wait for the admissions' first
        # tokens (a request that its first token ends is retired here, and the walk below finds its slot empty)
        self._tick_first_deferred = len(self._first_pending)
        self.metrics.on_first_tokens_deferred(self._tick_first_deferred)
        self._read_first_tokens()
        if self.paged:
            # every slot that does not decode (free, or waiting on a prefill's paste) has its table row at the sink:
            # this many rows of the tick's steps the paged decode kernels walk no page for (``paged_kv.NO_KEYS``)
            self._tick_rows_skipped = (self.num_slots - n_decoding) * self.tick_block
            self.metrics.on_attention_rows_skipped(self._tick_rows_skipped)
        if self._steps_idle_state:
            # the tick steps every slot's recurrent state (a state-space layer's step kernel is told which slots
            # decode and visits no other: then nothing is counted); this many slot-steps of it decode nothing
            self._tick_state_idle = (self.num_slots - n_decoding) * self.tick_block
            self.metrics.on_state_step(self._tick_state_idle)
        with phase("engine.decode.sync"):
            toks_k = np.asarray(toks_k)  # [K, slots] — ONE host sync per block
            lps_k = np.asarray(lps_k)
            if load_k is not None:
                load_k = np.asarray(load_k)
                touched, most, visits, pairs = (load_k[..., i] for i in range(4))
                self._tick_expert_load = (int(touched.sum()), int(most.max()), int(visits.sum()), int(pairs.sum()))
                self.metrics.on_expert_load(*self._tick_expert_load)
        with phase("engine.decode.walk"):
            # (first position, tokens kept) a decoding slot: what an aligned window's counts are made of
            kept = None if self._aligned is None and self._ring is None else []
            toks_by_slot, lps_by_slot = toks_k.T.tolist(), lps_k.T.tolist()  # Python ints and floats, once for all slots
            for slot, req in enumerate(self.slot_req):
                if req is None or self.slot_phase[slot] != "decode":
                    continue
                first = int(self.slot_pos[slot])
                n_new, retired = self._take_tokens(req, toks_by_slot[slot], lps_by_slot[slot])
                self.slot_pos[slot] += n_new
                self.slot_tok[slot] = req.out_tokens[-1]
                self.metrics.on_tokens(n_new)
                self.metrics.on_tick_tokens(req.uid, n_new)
                if self.tracer is not None:
                    self.tracer.window(req.trace, "decode", tokens=n_new)
                if kept is not None:
                    kept.append((first, n_new))
                if retired:
                    self._retire(slot)  # the host's books now; the slot's clear goes behind the next tick's first program
            if kept and self._aligned is not None:
                self._count_window_attention(np.asarray(kept))
            elif kept:
                self._count_ring_attention(np.asarray(kept))

    def _take_tokens(self, req: _Request, toks: list, lps: list) -> tuple:
        """Give ``req`` the tokens it keeps of its column of a tick's block (``toks``, ``lps``: lists), in one
        pass: up to its budget and through the first eos (what is left of the block is overshoot, discarded).
        ``(tokens kept, whether the last of them ends the request)``. Stop sequences are matched against the
        stream as it grows, token by token."""
        out = req.out_tokens
        if req.stop_sequences:
            for n, tok in enumerate(toks, 1):
                out.append(tok)
                if ended := self._finished(req, tok):
                    break
            req.out_lps.extend(lps[:n])
            return n, ended
        col = toks[: max(1, req.max_new_tokens - len(out))]
        if self.eos_token_id is not None and self.eos_token_id in col:
            del col[col.index(self.eos_token_id) + 1 :]
        out.extend(col)
        req.out_lps.extend(lps[: len(col)])
        return len(col), self._finished(req, col[-1])

    def _decoding_slots(self) -> np.ndarray:
        """``[slots]`` bool: the slots in which a request decodes. (A numpy array: ``jnp.asarray`` of a
        Python list weighs every element's type, 0.7 ms a tick at 64 slots.)"""
        return np.asarray([ph == "decode" for ph in self.slot_phase])

    def _decoding_arg(self, decoding: Optional[np.ndarray] = None) -> tuple:
        """The decode tick's last argument: ``([slots] bool,)``, :meth:`_decoding_slots` on the device, for
        the paged tick of a model with routed experts (its ``RoutedFFN`` layers multiply those slots' rows
        alone) or with state-space layers (the step kernel reads and writes those slots' ``ssm_state``
        alone); ``()`` for every other tick, which takes no such argument."""
        if not self._mask_idle_rows:
            return ()
        return (_jax().numpy.asarray(self._decoding_slots() if decoding is None else decoding),)

    # ---- aligned windows (EVA): pages by kind, the close, the counts ----------------------------------------------

    def _init_aligned(self, config, block: int) -> None:
        """A model whose attention reads an aligned window and pooled chunks before it (EVA). A slot holds the
        exact pages of ONE window (``window // block``: its ``_slot_blocks``) and a page of summaries for every
        ``block`` chunks of the windows before it (``_slot_summary``, the cache's ``summary_table``), all from
        the one allocator. What is not built over the second table is refused by name, here or at its call."""
        from .ops.eva_attention import check_paged_sizes, summary_pages

        window, chunk = config.eva_window_size, config.eva_chunk_size
        check_paged_sizes(block, window, chunk)
        self._aligned = (window, chunk)
        if self._sched.config.enable_preemption:
            raise NotImplementedError(self._aligned_refusal("preemption with resume (SchedulerConfig.enable_preemption)"))
        self._summary_entries = summary_pages(config.max_position_embeddings, block, chunk)
        self._slot_summary: list[dict] = [{} for _ in range(self.num_slots)]  # summary_table entry -> pool block id
        self._slot_last = [0] * self.num_slots  # the last position whose row a slot keeps (total + max_new - 2)
        self._reserved_summary = ({}, None, 0)

    @staticmethod
    def _aligned_refusal(what: str) -> str:
        return (f"{what} is not built over summary pages: an EVA cache keeps the open window's rows and the pooled "
                "chunks of the closed windows under two tables; serve it with bucketed prefill and no preemption")

    def _aligned_pages(self, total: int, max_new: int) -> tuple:
        """``(exact, summary)`` pages a request of ``total`` prompt tokens and ``max_new`` new ones reserves: the
        pages of the fullest window it will hold, ``min(total + max_new - 1, window) / block`` at most (a closed
        window's blocks are the next window's), and whole pages of summaries for the windows it will CLOSE AND
        READ PAST, ``(window / chunk / block) * ((total + max_new - 2) // window)``: the summaries of the last
        window it reaches are read by nobody and go to the trash sink. The first decode step writes position
        ``total``, so the first window held is ``total // window``: a prompt that ends on a window's edge
        pastes summaries alone."""
        window, chunk = self._aligned
        bs_ = self._pcfg.block_size
        last = total + max_new - 2  # the last position whose row is kept (>= total - 1)
        exact = min(window // bs_, last // bs_ + 1 - (window // bs_) * (total // window))
        return max(exact, 0), (window // chunk // bs_) * (last // window)

    def _reserve_aligned(self, total: int, max_new: int):
        """:meth:`_reserve_blocks` for an aligned window. The slot's table is written ONCE: entry ``i`` of every
        window the request will reach names block ``i % (window / block)`` of the ``exact`` it owns, so a close
        changes nothing on the device and the rows of window ``w + 1`` overwrite those of ``w``, which nothing
        reads any more (its last chunk was pooled by the step that wrote its last row)."""
        window, _ = self._aligned
        bs_ = self._pcfg.block_size
        per_w = window // bs_
        n_exact, n_summary = self._aligned_pages(total, max_new)
        ids = self._alloc.alloc(n_exact + n_summary)
        if ids is None:
            return None
        exact, first = ids[:n_exact], per_w * (total // window)
        table = np.zeros((self._mb,), np.int32)  # pad -> trash sink
        for i in range(first, min(self._mb, (total + max_new - 2) // bs_ + 1)):
            table[i] = exact[i % per_w]
        write_row = np.zeros((self._mb,), np.int32)
        write_row[first : first + n_exact] = exact  # the paste writes the open window's pages alone
        summary_row = np.zeros((self._summary_entries,), np.int32)
        summary_row[:n_summary] = ids[n_exact:]
        self._reserved_summary = (dict(enumerate(ids[n_exact:])), summary_row, total + max_new - 2)
        return {first + j: bid for j, bid in enumerate(exact)}, {}, table, write_row

    def _close_windows(self) -> None:
        """After the tick in which a slot's position crossed into a new window: the closed window's blocks are
        the new window's (the table said so from admission), and those the rest of the request will not fill go
        back to the allocator. Host bookkeeping alone: no program runs and the device's table stays as admission
        wrote it (the closed window's entries are never read or written again: a step gathers from the open
        window's first entry on and stores at its frontier's; ``clear_slot`` zeroes the row when the slot is
        released). A phase of its own (``engine.window.close``), entered only when a window closed, so that a slow
        tick says so."""
        window, _ = self._aligned
        bs_ = self._pcfg.block_size
        per_w = window // bs_
        crossed = []  # (slot, the blocks of the closed window under the entries of the window entered, how many of them it keeps)
        for slot, req in enumerate(self.slot_req):
            owned = self._slot_blocks[slot]
            if req is None or self.slot_phase[slot] != "decode" or not owned:
                continue
            w_now = int(self.slot_pos[slot]) // window
            if w_now > min(owned) // per_w:
                keep = max(0, min(per_w, self._slot_last[slot] // bs_ + 1 - per_w * w_now))
                crossed.append((slot, [(per_w * w_now + j, owned[i]) for j, i in enumerate(sorted(owned))], keep))
        if not crossed:
            return
        with phase("engine.window.close", slots=len(crossed), blocks_freed=sum(len(blocks) - keep for _, blocks, keep in crossed)):
            for slot, blocks, keep in crossed:
                self._slot_blocks[slot] = dict(blocks[:keep])
                self._alloc.free([bid for _, bid in blocks[keep:]])

    def _count_window_attention(self, kept: np.ndarray) -> None:
        """The tick's counts for an aligned window, from its kept steps alone (``kept``: a row ``(first position,
        steps kept)`` a decoding slot): rows of keys attended (``frontier + 1``: a summary a chunk of the closed
        windows, the open window's rows), rows of context (``t + 1``), chunks pooled, windows closed."""
        window, chunk = self._aligned
        t, valid = self._kept_steps(kept)
        rows = (window // chunk) * (t // window) + t % window + 1
        self._tick_windows = (
            int(rows[valid].sum()), int((t + 1)[valid].sum()), int((valid & (t % chunk == chunk - 1)).sum()),
            int((valid & (t % window == window - 1)).sum()),
        )
        self.metrics.on_window_attention(*self._tick_windows)

    # ---- a pool and a table a kind of layer: window layers' rings beside full layers' tables --------------------

    def _init_ring(self, config, block: int, window_pool_blocks: Optional[int]) -> dict:
        """A model with window AND full attention layers. The full layers keep what the engine has: blocks for the
        whole context under ``block_table``, from ``_alloc``. The window layers get a pool of their own
        (``_alloc_w``) and a ``window_table`` of ``ring`` entries a slot, used as a ring (position ``p`` at entry
        ``(p // block) % ring``): a slot's ring is reserved at admission (``_slot_ring``: no more blocks than the
        request will ever touch), written once by its paste, and freed at retirement; between the two no program
        touches the table and nothing expires. Returns the two fields of the ``PagedConfig``. What is not built
        over the ring is refused by name, here or at its call."""
        from .ops.paged_kv import BlockAllocator, ring_entries

        if self._sched.config.enable_preemption:
            raise NotImplementedError(self._ring_refusal("preemption with resume (SchedulerConfig.enable_preemption)"))
        self._ring = ring_entries(config.sliding_window, block, config.max_position_embeddings)
        blocks = int(window_pool_blocks) if window_pool_blocks is not None else self.num_slots * self._ring + 1
        self._alloc_w = BlockAllocator(blocks)
        self._slot_ring: list[list] = [[] for _ in range(self.num_slots)]  # a slot's blocks of the window pool, by entry
        self._reserved_ring = ([], None)
        return {"window_ring": self._ring, "window_blocks": blocks}

    @staticmethod
    def _ring_refusal(what: str) -> str:
        return (f"{what} is not built over a ring table: a model with window and full attention layers keeps the window "
                "layers' rows in a pool of their own under a ring of entries a slot, which holds the last window alone; "
                "serve it with bucketed prefill, no prefix reuse and no preemption")

    def _check_handoff(self) -> None:
        check_handoff_layout(self._row_template)
        if self._ring is not None:
            raise NotImplementedError(self._ring_refusal("KV hand-off (kv_handoff_dims, prefill_detached, submit_prefilled)"))

    def _ring_blocks_for(self, total: int, max_new: int) -> int:
        """Blocks of the window layers' pool a request reserves: the pages it will ever touch (through its last
        kept write, position ``total + max_new - 2``), the ring's entries at most."""
        return min(self._ring, (total + max_new - 2) // self._pcfg.block_size + 1)

    def _reserve_ring(self, total: int, max_new: int) -> bool:
        ids = self._alloc_w.alloc(self._ring_blocks_for(total, max_new))
        if ids is None:
            return False
        window_row = np.zeros((self._ring,), np.int32)  # an entry the request never reaches -> trash sink
        window_row[: len(ids)] = ids
        self._reserved_ring = (ids, window_row)
        return True

    def _blocks_by_kind(self, req: _Request) -> dict:
        """``engine.admit``'s counts of the blocks the admission reserves by kind; nothing for a model of one kind."""
        if self._ring is None:
            return {}
        plen, prompt_len, max_new = self._request_block_dims(req)
        return {"full_blocks": self._new_blocks_for(plen, prompt_len, max_new),
                "window_blocks": self._ring_blocks_for(plen + prompt_len, max_new)}

    def _kept_steps(self, kept: np.ndarray) -> tuple:
        """``(t, valid)``, each ``[decoding slots, tick_block]``: the position of every step of the tick and whether the
        slot kept it (``kept``: a row ``(first position, steps kept)`` a decoding slot)."""
        k = np.arange(self.tick_block)[None, :]
        return kept[:, :1] + k, k < kept[:, 1:]

    def _count_ring_attention(self, kept: np.ndarray) -> None:
        """The tick's counts for layers of two kinds, from its kept steps alone: rows of context (``t + 1``: what a
        full layer reads) and rows inside the band (``min(t + 1, window)``: what a window layer reads)."""
        t, valid = self._kept_steps(kept)
        context = int((t + 1)[valid].sum())
        self._tick_window_rows = int(np.minimum(t + 1, self.model.config.sliding_window)[valid].sum())
        self._tick_windows = (0, context, 0, 0)
        self.metrics.on_window_attention(0, context, 0, 0)
        self.metrics.on_window_rows(self._tick_window_rows)

    def _expire_window_blocks(self) -> None:
        """Sliding-window models: expire blocks the band can no longer
        read — entries fully below frontier - W + 1 return to the pool
        (owned) or drop a refcount (shared); their table entries point at
        the trash sink before the next tick, so the (masked) reads stay
        valid."""
        if not self.paged or self._window is None:
            return
        jnp = _jax().numpy
        bs_ = self._pcfg.block_size
        for slot, req in enumerate(self.slot_req):
            if req is None or self.slot_phase[slot] != "decode":
                continue
            keep_from = max(0, int(self.slot_pos[slot]) - self._window + 1) // bs_
            dead_own = [i for i in self._slot_blocks[slot] if i < keep_from]
            dead_shared = [i for i in self._slot_shared[slot] if i < keep_from]
            if not dead_own and not dead_shared:
                continue
            for i in dead_own:
                self._alloc.free([self._slot_blocks[slot].pop(i)])
                self._slot_table[slot][i] = 0
            for i in dead_shared:
                self._shared_refs[self._slot_shared[slot].pop(i)] -= 1
                self._slot_table[slot][i] = 0
            self.slot_caches = self._set_table(
                self.slot_caches, jnp.int32(slot), jnp.asarray(self._slot_table[slot])
            )

    def run(self) -> dict:
        """Drive ticks until queue and slots drain; returns {uid: tokens}."""
        while self.queue or self.active_count:
            if self.step() == 0 and self.queue and self._pool_blocked:
                # admission hit pool exhaustion and NOTHING is active any
                # more — every block that can ever be free is free NOW. If
                # the head still doesn't fit, it is unsatisfiable
                # (registered prefixes hold the rest of the pool) and
                # raising beats the silent busy-loop; if it fits, the
                # blocking was transient (the tick's retirements freed
                # blocks after the admit pass) and the next step admits it.
                need = self._head_new_blocks()
                if need > self._alloc.free_count:
                    raise RuntimeError(
                        f"request {self.queue[0].uid} needs {need} pool blocks but "
                        f"only {self._alloc.free_count} can ever be free (registered prefixes "
                        "hold the rest); raise pool_blocks or unregister unused prefixes"
                    )
        return dict(self.done)

    def generate_many(self, prompts, max_new_tokens: int = 32) -> list:
        """Convenience: submit all prompts, run to completion, return the
        completed token arrays in submission order."""
        uids = [self.submit(p, max_new_tokens) for p in prompts]
        self.run()
        return [self.done[u] for u in uids]

    # ---- internals ------------------------------------------------------

    def _finished(self, req: _Request, tok: int) -> bool:
        if self.eos_token_id is not None and tok == self.eos_token_id:
            return True
        for seq in req.stop_sequences:
            if len(req.out_tokens) >= len(seq) and req.out_tokens[-len(seq):] == list(seq):
                return True
        return len(req.out_tokens) >= req.max_new_tokens

    def _trace_ctx(self):
        """Mesh context for tracing engine programs: a sharded model's
        mesh (shard_model sets ``model.mesh``), else a no-op."""
        from .generation import _trace_ctx

        return _trace_ctx(getattr(self.model, "mesh", None))

    def _tick_args(self) -> tuple:
        """The decode tick's arguments as they stand now: what its ``serving_programs.Program`` is lowered from."""
        return self.model.params, self.slot_caches, self.slot_tok, self.slot_pos, self._slot_keys, *self._decoding_arg()

    def _check_programs(self, check, mesh, bucket, **options) -> dict:
        """``check(raw program, *its sample arguments, mesh=, **options)`` for every program of
        ``_perf_programs``, each traced under its own contexts: ``{name: report}``. Nothing compiles or executes.
        ``mesh`` defaults to the sharded model's mesh, else a single-device mesh; ``bucket`` to the smallest."""
        if mesh is None:
            mesh = getattr(self.model, "mesh", None)
        if mesh is None:
            from .parallel.mesh import MeshConfig

            mesh = MeshConfig(data=1).build(_jax().devices()[:1])
        b = int(bucket) if bucket is not None else min(self.prompt_buckets)
        reports = {}
        for name, program in self._perf_programs.items():
            with program.traced():
                reports[name] = check(program.fn, *program.args(b), mesh=mesh, **options)
        return reports

    def perf_check(self, mesh=None, generation=None, bucket=None, dcn=None) -> dict:
        """Static roofline of the engine's real serving programs — the
        prefill at ``bucket`` (default: the smallest prompt bucket) and
        the decode tick — via :func:`analysis.perfmodel.perf_check`.
        Nothing compiles or executes: the same raw functions the engine
        jits are traced abstractly, so the report prices exactly the
        programs that serve traffic (per-op FLOPs / HBM bytes /
        bytes-on-wire, predicted step time, MFU upper bound, TPU5xx
        findings). Returns ``{"prefill": PerfReport, "decode_tick":
        PerfReport}`` (whichever programs this engine configuration
        has)."""
        from .analysis.perfmodel import perf_check

        return self._check_programs(perf_check, mesh, bucket, generation=generation, dcn=dcn)

    def numerics_check(self, mesh=None, bucket=None, assume=None) -> dict:
        """Static numerics analysis of the engine's real serving programs
        (same program registry as :meth:`perf_check`) via
        :func:`analysis.numerics.numerics_check`: value intervals +
        dtype provenance over the prefill and decode-tick jaxprs, plus
        the TPU6xx precision findings — attention softmax overflow in
        low precision and unguarded normalisations are exactly the
        decode-path hazards this catches before a compile. Returns
        ``{"prefill": NumericsReport, "decode_tick": NumericsReport}``."""
        from .analysis.numerics import numerics_check

        return self._check_programs(numerics_check, mesh, bucket, assume=assume)

    def _bucket_for(self, n: int) -> Optional[int]:
        """Covering prefill bucket for an ``n``-token prompt: the smallest
        that holds it. ``None`` routes the prompt to the chunked-prefill
        path."""
        return next((b for b in self.prompt_buckets if b >= n), None)

    def _note_bucket_compile(self, kind: str, bucket: int, ms: float):
        """Per-bucket program-build attribution: lands in
        ``bucket_compile_ms`` (host-side inspection) and as ONE
        ``serving_bucket_compile`` telemetry event — startup/first-hit
        latency is attributable to the exact bucket that caused it. The
        wall time includes trace+lower plus either the XLA compile or
        (warm store) the deserialize; the paired ``compile_cache_*``
        event says which."""
        self.bucket_compile_ms[(kind, int(bucket))] = round(ms, 3)
        self._log.event(
            "serving_bucket_compile", program=kind, bucket=int(bucket), compile_ms=round(ms, 3)
        )

    @property
    def scheduler_config(self) -> SchedulerConfig:
        """The active :class:`~accelerate_tpu.scheduling.SchedulerConfig`
        (budget, priorities, SLO thresholds, preemption)."""
        return self._sched.config

    @property
    def program_cache(self):
        """The engine's :class:`~accelerate_tpu.aot.ProgramCache` (every
        prefill bucket and tick program routes through it)."""
        return self._pc

    def _plan_blocks(self, plen: int, prompt_len: int, max_new: int):
        """Live table-entry range ``[lo, hi)`` for a request, plus the
        count of leading prefix FULL blocks eligible for aliasing.
        ``hi`` reserves through the last *kept* write — position
        total + max_new - 2 (a finished slot's discarded overshoot
        writes land in trash entries or its own last block, never a
        neighbour's). ``lo`` is 0 unless the model has a sliding window:
        the decode band never reads positions <= total - W, so blocks
        entirely below it start as trash — a windowed request's pool
        cost is O(window + max_new) regardless of prompt length."""
        bs_ = self._pcfg.block_size
        total = plen + prompt_len
        hi = min(self._mb, -(-(total + max_new - 1) // bs_))
        lo = 0
        if self._window is not None:
            lo = min(max(0, total - self._window + 1) // bs_, hi)
        alias_hi = min(plen // bs_, hi)  # plen=0 (no prefix) -> nothing aliasable
        return lo, hi, alias_hi

    def _new_blocks_for(self, plen: int, prompt_len: int, max_new: int) -> int:
        """New (non-aliased) blocks a request allocates — the ONE place
        the capacity arithmetic lives (submit's feasibility check, the
        admission allocation, and run()'s unsatisfiable-head diagnostic
        must agree or admission deadlocks/overcommits)."""
        if self._aligned is not None:
            return sum(self._aligned_pages(plen + prompt_len, max_new))
        lo, hi, alias_hi = self._plan_blocks(plen, prompt_len, max_new)
        return (hi - lo) - max(0, alias_hi - lo)

    def _request_block_dims(self, req: _Request) -> tuple:
        """``(plen, prompt_len, max_new)`` for block planning — a
        preempted request resumes as prompt + all-but-last generated
        tokens with the remaining budget, which reserves exactly the
        blocks the original request would have (``hi`` is invariant
        across preemptions, so resume can never deadlock a pool the
        original admission fit)."""
        plen = self._prefixes[req.prefix_id]["len"] if req.prefix_id is not None else 0
        g = len(req.out_tokens)
        if req.preempted and g:
            return plen, len(req.prompt) + g - 1, req.max_new_tokens - g + 1
        return plen, len(req.prompt), req.max_new_tokens

    def _head_new_blocks(self) -> int:
        return self._new_blocks_for(*self._request_block_dims(self.queue[0]))

    @property
    def pool_free_blocks(self) -> Optional[int]:
        """Free blocks in the paged pool (None in dense mode); with a pool a kind of layer, the scarcer's."""
        if not self.paged:
            return None
        return self._alloc.free_count if self._ring is None else min(self._alloc.free_count, self._alloc_w.free_count)

    def _retire(self, slot: int):
        req = self.slot_req[slot]
        parts = [req.prompt, np.asarray(req.out_tokens, np.int32)]
        if req.prefix_id is not None:
            parts.insert(0, self._prefixes[req.prefix_id]["tokens"])
        self.done[req.uid] = np.concatenate(parts)
        self._done_new[req.uid] = np.asarray(req.out_tokens, np.int32)
        self._done_lps[req.uid] = np.asarray(req.out_lps, np.float32)
        self._release(slot, defer_clear=True)
        self._index[req.uid] = ("done", None)
        self.metrics.on_complete(req.uid)
        if self.tracer is not None:
            self.tracer.finish(req.trace, status="ok", tokens=len(req.out_tokens))

    def _release(self, slot: int, defer_clear: bool = False):
        """Free a slot's resources without publishing a result (shared by
        retirement, cancellation, and decode preemption). The static tick
        goes on computing for the free slot: in the paged layout
        ``clear_slot`` points its rows at the trash sink and zeroes its
        recurrent state (a state-space layer then steps token 0 from zero:
        finite, never read); in the dense layout rows and state alike keep
        accumulating garbage. Either way the next prefill's paste / insert
        replaces the slot whole, state leaves included. The host's books
        are settled here; a retirement leaves the device's half pending
        (``defer_clear``: :meth:`_flush_clears` sends it before any paste
        into the slot and before the next decode tick), cancellation and
        preemption send it now with whatever was pending."""
        self.slot_phase[slot] = None
        self._prefill_state[slot] = None
        if slot in self._prefill_order:
            self._prefill_order.remove(slot)
        if self.paged:
            # Validate shared refcounts BEFORE any mutation (must survive
            # python -O): a tripped invariant must leave the slot, pool, and
            # table state intact for diagnosis, not half-freed.
            for bid in self._slot_shared[slot].values():
                if self._shared_refs.get(bid, 0) < 2:
                    raise RuntimeError(f"shared block {bid} over-freed")
        self.slot_req[slot] = None
        # a free slot still computes in the static tick: every free slot feeds the same token at the same
        # position, so that a routed FFN sends them all to the same few experts and not each to 8 of its own
        self.slot_tok[slot] = 0
        self.slot_pos[slot] = 0
        if self.paged:
            # free this request's blocks and re-point the whole row at the
            # trash sink — the static tick keeps computing for every slot,
            # and a stale table would corrupt blocks once they're
            # reallocated to another request
            self._alloc.free(list(self._slot_blocks[slot].values()))
            self._slot_blocks[slot] = {}
            if self._aligned is not None:
                self._alloc.free(list(self._slot_summary[slot].values()))
                self._slot_summary[slot] = {}
            if self._ring is not None:
                self._alloc_w.free(self._slot_ring[slot])
                self._slot_ring[slot] = []
            for bid in self._slot_shared[slot].values():
                self._shared_refs[bid] -= 1
            self._slot_shared[slot] = {}
            self._slot_table[slot][:] = 0
            self._clear_pending.append(slot)
            if not defer_clear:
                self._flush_clears()

    def _flush_clears(self, deferred: bool = False) -> None:
        """Send the pending clears to the device, ONE program for all of them (``clear_slots`` over a padded
        list of slots). ``deferred`` says the call stands behind a program of the running tick (its first
        prefill) or with its decode dispatch: those slots count as ``clears_deferred`` of ``engine.tick.done``."""
        if not self._clear_pending:
            return
        slots = np.zeros((self.num_slots,), np.int32)
        slots[: len(self._clear_pending)] = self._clear_pending
        self.slot_caches = self._clear_slots(self.slot_caches, slots, np.int32(len(self._clear_pending)))
        if deferred:
            self._tick_clears_deferred += len(self._clear_pending)
        self._clear_pending.clear()
