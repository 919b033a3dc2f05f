"""The device programs a :class:`~accelerate_tpu.serving.ServingEngine` runs: the layer between
``ServingEngine.step`` and ``model.apply_fn``. The engine's constructor builds :class:`EnginePrograms` once and
from then on only calls what it holds. What lives here and nowhere else:

* **the raw programs**, as module-level builders that take what they close over (``apply_fn``, the sampler,
  ``tick_block``) as arguments, so that any of them can be built, lowered and timed with no engine;
* **the decode contract** a model is called under, asked once and abstractly (:func:`row_template`);
* **which extra arguments this model's programs take** (:func:`extra_arguments`): the one place that reads a
  model family's facts (a recurrent state, routed experts, a kernel that steps the state) to decide a signature;
* **how a program is named, jitted, donated and entered** (:func:`ctx_jit`): through the engine's ProgramCache,
  under the name the device trace and the phase log show (``prefill_b<N>``, ``paged_decode_tick``, ``paste_row``);
* **one description a program** (:class:`Program`) with one way to lower it under its trace contexts.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, NamedTuple, Optional

# every function here is traced code and only ``ServingEngine.__init__`` imports the module: neither
# ``import accelerate_tpu`` nor the CLI reaches these lines, which is what the ``_jax()`` convention is for
import jax  # tpu-lint: disable=TPU204
import jax.numpy as jnp  # tpu-lint: disable=TPU204

from . import generation
from .models import llama
from .ops import kv_cache, moe, paged_kv


def row_template(apply_fn, params):
    """The dense per-row cache of one sequence, as shapes (a 1-token prefill outside ``paged_mode``): a dense
    slot's rows, what chunk windows run against in both layouts, and what a KV hand-off ships. The one abstract
    call also holds the model to the decode contract, ``logits_at`` included (a bucket's prefill keeps one row of
    logits and asks for that row alone): an ``apply_fn`` that cannot be called so is refused here, by the
    contract's name."""
    one = jnp.zeros((1, 1), jnp.int32)
    try:
        _, template = jax.eval_shape(
            lambda p, i: apply_fn(p, i, positions=one, decode=True, cache=None, logits_at=jnp.int32(0)), params, one
        )
    except TypeError as refused:
        raise TypeError(
            "ServingEngine serves a model with the decode contract apply_fn(params, ids, positions=[B, S] int32, "
            "decode=True, cache=None | the cache it returned, logits_at=None | int32 scalar | int32 [n]) -> "
            f"(logits [B, S | 1 | n, vocab], cache); this model's apply_fn cannot be called so: {refused}"
        ) from refused
    return template


@dataclasses.dataclass(frozen=True)
class ExtraArguments:
    """What this model's programs take beyond the decode contract's own arguments (:func:`extra_arguments`)."""

    new_span: bool  # programs that run a window tell the model which of its tokens are new and real
    decoding: bool  # the paged decode tick takes one ``[slots]`` bool more: the slots in which a request decodes
    steps_idle_state: bool  # no argument: whether the tick steps the recurrent state of slots that decode nothing

    def span(self, lo, hi) -> dict:
        return {"new_span": (lo, hi)} if self.new_span else {}


def extra_arguments(config, template, paged: bool, trace_ctx: Callable = contextlib.nullcontext) -> ExtraArguments:
    """Decided once, from the model's configuration and its row ``template``:

    * ``new_span``: a model whose layers keep a recurrent state (``ops.paged_kv.STATE_LEAVES``) beside its K/V
      rows has its windows told which of their tokens are new; no other model's programs take the argument;
    * ``decoding``: the PAGED tick of a model with routed experts, or with a recurrent state that a kernel steps
      (``ssm_state``), is told which slots decode: the stale token of every other slot reaches no expert, and
      its state is neither read nor written. No other program takes it (the dense tick is a ``vmap`` of one
      slot's step: no routed experts, no kernel);
    * ``steps_idle_state``: whether the tick steps the state of every slot (a convolution's carried inputs; a
      state-space layer's through the plain step) or of the decoding slots alone (through the kernel, where
      programs traced under ``trace_ctx`` take it): what ``state_slots_idle`` counts."""
    has_state = paged_kv.state_bytes(template) > 0
    masks_state = paged and "ssm_state" in kv_cache.leaf_names(template)
    steps_idle_state = has_state
    if masks_state:
        with trace_ctx():
            steps_idle_state = not llama.state_step_kernel()
    return ExtraArguments(
        new_span=has_state,
        decoding=masks_state or (paged and getattr(config, "n_routed_experts", None) is not None),
        steps_idle_state=steps_idle_state,
    )


def pick_lp(row, tok):
    """log P(tok) under the model's FULL distribution at this step (f32 log-softmax) — the standard serving
    logprob surface, even when sampling is temperature/top-k shaped."""
    return jax.nn.log_softmax(row.astype(jnp.float32))[tok]


def request_key(key, fold):
    """The sampling chain a request starts from: ``fold_in(key, fold)`` for a fresh request (``key`` the
    engine's, ``fold`` the uid: computed here, inside the program that consumes it, and not by eager
    programs ahead of its dispatch), ``key`` as it is where ``fold`` is negative (a chain carried in)."""
    return jnp.where(fold >= 0, jax.random.fold_in(key, fold), key)


def named(fn, name: str):
    """``fn`` under ``name``: jit names the module after the function, and the device line of a profile shows
    the module, so a program is jitted under the name ProgramCache logs."""

    def call(*args):
        return fn(*args)

    call.__name__ = call.__qualname__ = name
    return call


def make_prefill(apply_fn, sampler, extra: ExtraArguments):
    """``prefill``: [1, B] padded prompt -> (first next-token, its logprob, per-row cache with write index reset
    to true_len, advanced key). The model heads the one row that is kept. ``key``, ``fold``: :func:`request_key`."""

    def prefill(params, ids, true_len, key, fold):
        b_len = ids.shape[1]
        positions = jnp.broadcast_to(jnp.arange(b_len), (1, b_len))
        logits, cache = apply_fn(
            params, ids, positions=positions, decode=True, cache=None, **extra.span(0, true_len), logits_at=true_len - 1
        )
        key, sub = jax.random.split(request_key(key, fold))
        row = logits[0, 0]
        next_tok = sampler(row[None], sub)[0]
        cache = kv_cache.reset_cache_index(cache, true_len)
        return next_tok, pick_lp(row, next_tok), cache, key

    return prefill


def make_chunk_windows(apply_fn, extra: ExtraArguments):
    """``(chunk_cold, chunk_warm)``: one window of a long prompt or a prefix's suffix, against no cache and against
    a row cache. ``[lo, hi)``: the window's new tokens, from its own first (``ServingEngine._run_window``)."""

    def chunk_cold(params, ids, lo, hi):
        positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        return apply_fn(params, ids, positions=positions, decode=True, cache=None, **extra.span(lo, hi))

    def chunk_warm(params, ids, pos0, cache, lo, hi):
        positions = pos0 + jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        return apply_fn(params, ids, positions=positions, decode=True, cache=cache, **extra.span(lo, hi))

    return chunk_cold, chunk_warm


def make_sample_at(sampler):
    def sample_at(logits, offset, key, fold):
        key, sub = jax.random.split(request_key(key, fold))
        row = logits[0, offset]
        tok = sampler(row[None], sub)[0]
        return tok, pick_lp(row, tok), key

    return sample_at


def reset_idx(cache, n):
    return kv_cache.reset_cache_index(cache, n)


def insert(slot_caches, keys, row_cache, key, slot):
    """The dense layout's admission (the paged one's is :func:`paste_row`): either also starts the slot's sampling
    chain (``keys``: the engine's ``_slot_keys``): one program, and the host hands it its numpy arguments as they are."""
    caches = jax.tree.map(
        lambda big, row: jax.lax.dynamic_update_index_in_dim(big, row.astype(big.dtype), slot, 0), slot_caches, row_cache
    )
    return caches, keys.at[slot].set(key)


def paste_row(paged_cache, keys, row_cache, key, write_row, table_row, slot, new_index, *summary_row):
    pasted = paged_kv.paste_row(paged_cache, row_cache, write_row, table_row, slot, new_index, *summary_row)
    return pasted, keys.at[slot].set(key)


def paste_row_ring(paged_cache, keys, row_cache, key, write_row, table_row, slot, new_index, window_row):
    """:func:`paste_row` for a cache with a pool and a table a kind of layer: ``window_row`` is the slot's ring, and
    the window layers take the last window of the row cache alone (``paged_kv.paste_row(window_row=)``)."""
    pasted = paged_kv.paste_row(paged_cache, row_cache, write_row, table_row, slot, new_index, window_row=window_row)
    return pasted, keys.at[slot].set(key)


def feed_first_token(toks, slot, tok):
    """``toks`` with a pending admission's first token in its slot: the token goes from the prefill
    to the decode tick without a visit to the host. One shape, called once a pending admission."""
    return toks.at[slot].set(tok.astype(toks.dtype))


def make_tick(step_body, tick_block: int):
    """K-step tick scaffold shared by both cache layouts: ``step_body(params, caches, toks, poss, keys,
    *decoding) -> (caches, next_toks, logprobs, keys, load)`` advances every slot one token; ``load`` is None, or
    the routed experts' counts of the step (``[expert layers, 4]``, ops/moe.py ``expert_load_counts``).
    ``decoding`` (``ServingEngine._decoding_arg``) is the same in every step.

    Decode K steps per host round-trip: one sync per TOKEN pays the dispatch and fetch latency on every token;
    the block scan amortises it K-fold. A slot that finishes (eos / budget) mid-block keeps computing until the
    block ends — those overshoot tokens are discarded host-side and the slot's cache is fully replaced at the
    next prefill-insert, so outputs stay token-exact."""

    def decode_tick(params, slot_caches, toks, poss, keys, *decoding):
        def block_step(carry, _):
            caches, toks, poss, keys = carry
            caches, nxt, lps, keys, load = step_body(params, caches, toks, poss, keys, *decoding)
            return (caches, nxt, poss + 1, keys), (nxt, lps, load)

        (slot_caches, _, _, keys), (toks_k, lps_k, load_k) = jax.lax.scan(
            block_step, (slot_caches, toks, poss, keys), None, length=tick_block
        )
        return slot_caches, toks_k, lps_k, keys, load_k  # each [K, slots]; load_k [K, layers, 4] or None

    return decode_tick


def make_paged_step(apply_fn, sampler):
    """Per-row frontiers are native to the paged layout (index is [B], not a scalar), so the tick is ONE batched
    program — no per-row vmap. Same key-split order as the dense ``one_step``, so outputs stay token-exact
    across layouts."""

    def paged_step(params, cache, toks, poss, keys, decoding=None):
        rows = {} if decoding is None else {"row_valid": decoding[:, None]}
        # one program sees the whole batch, so routed experts can count their step's load
        with moe.expert_load_counts() as loads:
            logits, cache = apply_fn(params, toks[:, None], positions=poss[:, None], decode=True, cache=cache, **rows)
        split = jax.vmap(jax.random.split)(keys)
        keys, subs = split[:, 0], split[:, 1]
        nxt = jax.vmap(lambda lg, s: sampler(lg[None], s)[0])(logits[:, -1], subs)
        lps = jax.vmap(pick_lp)(logits[:, -1], nxt)
        return cache, nxt, lps, keys, jnp.stack(loads) if loads else None

    return paged_step


def make_dense_step(apply_fn, sampler):
    def one_step(params, cache_row, tok, pos, key):
        logits, cache_row = apply_fn(
            params, tok.reshape(1, 1), positions=pos.reshape(1, 1), decode=True, cache=cache_row
        )
        key, sub = jax.random.split(key)
        row = logits[0, -1]
        nxt = sampler(row[None], sub)[0]
        return cache_row, nxt, pick_lp(row, nxt), key

    def dense_step(params, caches, toks, poss, keys):
        return *jax.vmap(one_step, in_axes=(None, 0, 0, 0, 0))(params, caches, toks, poss, keys), None

    return dense_step


class Program(NamedTuple):
    """One engine program as the analysis stack and the compile tests take it: nothing is jitted or compiled
    until somebody asks. Unpacks as the ``(raw, args, contexts)`` triple."""

    fn: Callable  # the raw function the engine jits
    args: Callable  # bucket | None -> sample arguments: abstract, or the engine's live ones (the decode tick's)
    contexts: tuple  # factories of the contexts the program is traced under

    @contextlib.contextmanager
    def traced(self):
        with contextlib.ExitStack() as stack:
            for factory in self.contexts:
                stack.enter_context(factory())
            yield

    def lower(self, *args, bucket=None, **jit_options):
        """``jax.jit(fn, **jit_options).lower(*args)`` under the program's contexts; ``args`` default to the
        sample arguments of ``bucket``."""
        with self.traced():
            return jax.jit(self.fn, **jit_options).lower(*(args or self.args(bucket)))


def ctx_jit(program_cache, enter: Callable, fn, name: Optional[str] = None, donate_argnums=()):
    """jit + re-enter the trace context around every call (``enter``: its factory): a shard_model'ed model pins
    ITS mesh for the cache sharding constraints and the paged kernel's shard_map, and the paged tick is traced
    under the paged layout besides (contexts only matter at the first call, which traces).

    Dispatch goes through the engine's ProgramCache, lowering at CALL time with the real inputs, so
    GSPMD-propagated layouts are honoured exactly like lazy jit (an eagerly ``.lower()``ed program would pin the
    shardings it saw at construction and reject the real ones): with a persistent store attached, a restarted
    replica deserializes these programs instead of recompiling them. ``__wrapped__`` is the jit object, donation
    and all."""
    jitted = program_cache.wrap_jit(
        jax.jit(fn if name is None else named(fn, name), donate_argnums=donate_argnums), name=name or fn.__name__
    )

    def call(*args):
        with enter():
            return jitted(*args)

    call.__wrapped__ = jitted.__wrapped__
    return call


class _LazyBuckets:
    """dict-like ``bucket -> compiled program`` that compiles on FIRST
    use instead of eagerly at engine construction: startup pays only for
    the buckets traffic actually hits, and each build is attributed by a
    per-bucket ``serving_bucket_compile`` telemetry event."""

    def __init__(self, build):
        self._build = build
        self._programs: dict = {}

    def __getitem__(self, bucket: int):
        prog = self._programs.get(bucket)
        if prog is None:
            prog = self._programs[bucket] = self._build(bucket)
        return prog

    def __len__(self) -> int:
        return len(self._programs)

    def compiled_buckets(self) -> tuple:
        return tuple(sorted(self._programs))


class EnginePrograms:
    """Every program of one engine, built once: each is jitted lazily through the engine's ProgramCache (nothing
    compiles until first use). ``trace_ctx``: factory of the model's mesh context. ``tick_args``: the decode
    tick's live arguments, for its :class:`Program`. ``on_bucket_build(kind, bucket, ms)`` is told the wall time
    of each bucket's build (trace + lower, then the XLA compile or a warm store's deserialize)."""

    insert = paste_row = paste_blocks = clear_slots = set_table_row = None  # a layout has its own: dense | paged

    def __init__(
        self, model, *, temperature: float, top_k: Optional[int], tick_block: int, prompt_buckets: tuple,
        paged_config: Optional[paged_kv.PagedConfig], program_cache, trace_ctx: Callable, tick_args: Callable,
        on_bucket_build: Callable,
    ):
        params, apply_fn = model.params, model.apply_fn
        sampler = generation._make_sampler(temperature, top_k)
        self.row_template = template = row_template(apply_fn, params)
        self.extra = extra = extra_arguments(model.config, template, paged_config is not None, trace_ctx)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        key_aval = jax.eval_shape(lambda: jax.random.key(0))

        def jit(fn, **how):
            return ctx_jit(program_cache, trace_ctx, fn, **how)

        prefill = make_prefill(apply_fn, sampler, extra)

        def prefill_args(b):
            return params, jax.ShapeDtypeStruct((1, b), jnp.int32), i32, key_aval, i32

        def build_prefill(b):
            t0 = time.perf_counter()
            with trace_ctx():
                prog = program_cache.compile(named(prefill, f"prefill_b{b}"), *prefill_args(b), name=f"prefill_b{b}")
            on_bucket_build("prefill", b, (time.perf_counter() - t0) * 1000.0)
            return prog

        self.prefill = _LazyBuckets(build_prefill)  # bucket -> prefill_b<bucket>
        # chunked-prefill programs (long prompts / prefix suffixes): jit specializes per window width, a bucket's
        # or the largest's, so the compile count stays O(buckets) and prompt length is bounded only by max_len
        chunk_cold, chunk_warm = make_chunk_windows(apply_fn, extra)
        self.chunk_cold, self.chunk_warm = jit(chunk_cold), jit(chunk_warm)
        self.sample_at, self.reset_idx = jit(make_sample_at(sampler)), jit(reset_idx)
        self.feed_first_token = jit(feed_first_token)
        if paged_config is not None:
            # The pool is ONE buffer for the engine's life: every program that takes the paged cache donates it,
            # writes in place and hands the same buffer back (the callers all rebind ``slot_caches``); the array
            # passed in is deleted by the call, so nothing may keep a reference to it across one.
            @contextlib.contextmanager
            def tick_ctx():  # both trace contexts: the paged layout and the model's mesh
                with paged_kv.paged_mode(paged_config), trace_ctx():
                    yield

            raw_tick = make_tick(make_paged_step(apply_fn, sampler), tick_block)
            self.decode_tick = ctx_jit(program_cache, tick_ctx, raw_tick, name="paged_decode_tick", donate_argnums=(1,))
            ringed = paged_config.window_ring is not None
            self.paste_row = jit(paste_row_ring if ringed else paste_row, name="paste_row" if ringed else None, donate_argnums=(0,))
            self.paste_blocks = jit(paged_kv.paste_blocks, donate_argnums=(0,))
            self.clear_slots = jit(paged_kv.clear_slots, donate_argnums=(0,))
            self.set_table_row = jit(paged_kv.set_table_row, donate_argnums=(0,))
        else:
            tick_ctx = trace_ctx
            raw_tick = make_tick(make_dense_step(apply_fn, sampler), tick_block)
            self.decode_tick, self.insert = jit(raw_tick), jit(insert)
        chunk = max(prompt_buckets)
        self.described = {  # what perf_check() / numerics_check() and the compile tests read
            "prefill": Program(prefill, prefill_args, (trace_ctx,)),
            # the resume-recompute program (preempt -> requeue -> resume rebuilds the evicted KV by warm chunk
            # windows): the analysis stack must cover every program the scheduler can launch, and this one is the
            # only engine program that reads AND extends a warm row cache
            "resume_recompute": Program(
                chunk_warm, lambda b: (params, jax.ShapeDtypeStruct((1, chunk), jnp.int32), i32, template, i32, i32), (trace_ctx,)
            ),
            "decode_tick": Program(raw_tick, lambda b: tick_args(), (tick_ctx,)),
        }
