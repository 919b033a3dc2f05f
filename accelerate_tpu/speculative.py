"""Speculative decoding: draft-proposed tokens verified by the target in
one forward — fewer target passes per emitted token, token-exact output.

No reference analogue (the reference delegates generation); parity-plus
inference performance surface alongside quantized decode and continuous
batching. Greedy acceptance: the draft proposes ``gamma`` tokens
autoregressively, the target scores all of them in ONE forward, the
longest prefix where the draft matched the target's own argmax is
accepted, and the target's argmax at the first mismatch is emitted as
the correction — so every iteration emits ``accepted + 1`` tokens for
one target forward, and the output equals plain greedy decode of the
target exactly.

Cache bookkeeping uses the same frontier argument as the serving
engine's padded prefill: rejected positions leave stale rows in both
models' caches, but the write index is reset to the accepted frontier,
and every stale row is overwritten by the next iteration's tokens
before the causal frontier reaches it — verified token-exact in
``tests/test_speculative.py``.

Both models run inside a handful of fixed-shape jitted programs (one
per (prompt_bucket, gamma)); the host loop only reads the per-iteration
accept count.

A load-bearing corollary of greedy acceptance: the emitted stream is the
target's argmax stream for ANY draft behavior — a cold, stale, or even
garbage draft cache can only lower the acceptance rate, never change a
token. The serving scheduler's per-priority speculative gating
(``SchedulerConfig.speculative_priorities``) leans on exactly this: a
tick whose decode set includes a non-speculative priority class runs the
plain target tick and leaves the draft caches stale, and the next
speculative tick is still token-exact.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def _jax():
    import jax

    return jax




def build_spec_step(t_apply, d_apply, gamma: int):
    """The draft-propose / target-verify core shared by
    :func:`speculative_generate` (batch-1 host loop) and the serving
    engine's speculative tick (vmapped over slots):

    ``(t_params, d_params, t_cache, d_cache, last_tok, pos) ->
    (t_cache, d_cache, emit [gamma+1], lps [gamma+1], n_emit)``

    ``pos`` is the cache frontier (= valid entries in BOTH caches;
    ``last_tok`` is emitted-but-not-yet-cached). The draft proposes
    ``gamma`` tokens autoregressively, one target forward scores them
    all, the longest prefix matching the target's own argmax is accepted
    and the target's correction (or bonus) token appended — so
    ``n_emit = accepted + 1`` and the emitted stream equals plain greedy
    target decode. ``lps`` are the target's f32 log-softmax of each
    emitted token. Both cache frontiers are reset to ``pos + n_emit``;
    stale speculative rows beyond are overwritten before the causal
    frontier reaches them (serving.py's padded-prefill argument)."""
    jax = _jax()
    jnp = jax.numpy
    from .ops.kv_cache import reset_cache_index

    g = gamma

    def spec_step(t_params, d_params, t_cache, d_cache, last_tok, pos):
        def draft_one(carry, _):
            d_cache, tok, p = carry
            logits, d_cache = d_apply(
                d_params, tok.reshape(1, 1), positions=p.reshape(1, 1), decode=True, cache=d_cache
            )
            nxt = jnp.argmax(logits[0, -1].astype(jnp.float32)).astype(jnp.int32)
            return (d_cache, nxt, p + 1), nxt

        (d_cache, d_last, _), drafts = jax.lax.scan(
            draft_one, (d_cache, last_tok, pos), None, length=g
        )  # drafts [g] = tokens for positions pos+1..pos+g
        # one extra draft pass caches d_last's own row (needed when every
        # draft is accepted — the next iteration's frontier includes it)
        _, d_cache = d_apply(
            d_params, d_last.reshape(1, 1), positions=(pos + g).reshape(1, 1),
            decode=True, cache=d_cache,
        )

        # target scores last_tok + ALL g drafts in ONE pass: logits[j] is
        # the target's token for position pos+j+1, so t_argmax[g] is the
        # bonus token when every draft matches
        fed = jnp.concatenate([last_tok[None], drafts])  # [g+1]
        positions = (pos + jnp.arange(g + 1))[None]
        t_logits, t_cache = t_apply(
            t_params, fed[None], positions=positions, decode=True, cache=t_cache
        )
        rows = t_logits[0].astype(jnp.float32)  # [g+1, V]
        t_argmax = jnp.argmax(rows, axis=-1).astype(jnp.int32)

        matches = drafts == t_argmax[:g]
        n_acc = jnp.argmin(jnp.concatenate([matches, jnp.array([False])])).astype(jnp.int32)
        emit = jnp.where(
            jnp.arange(g + 1) < n_acc, jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)]), 0
        )
        emit = emit.at[n_acc].set(t_argmax[n_acc])
        n_emit = n_acc + 1
        lps = jax.vmap(lambda r, t: jax.nn.log_softmax(r)[t])(rows, emit)

        new_frontier = pos + n_emit
        from .ops.paged_kv import STATE_LEAVES, state_bytes

        if state_bytes(t_cache) or state_bytes(d_cache):
            raise NotImplementedError(
                "speculative decoding takes a rejected draft back by resetting the cache's frontier; a "
                f"recurrent state ({' / '.join(STATE_LEAVES)}) has stepped over the rejected tokens and "
                "cannot be taken back: decode models whose layers keep one without a draft"
            )
        t_cache = reset_cache_index(t_cache, new_frontier)
        d_cache = reset_cache_index(d_cache, new_frontier)
        return t_cache, d_cache, emit, lps, n_emit

    return spec_step


def speculative_generate(
    target_model,
    draft_model,
    input_ids,
    max_new_tokens: int = 32,
    gamma: int = 4,
    eos_token_id: Optional[int] = None,
    return_stats: bool = False,
):
    """Greedy speculative decode of ``input_ids`` [1, S] (batch 1).

    ``draft_model`` must share the target's vocabulary (typically a
    smaller model of the same family). Returns int32 [1, S + n] with
    n <= max_new_tokens (exactly max_new_tokens without EOS). With
    ``return_stats``: (tokens, {"target_forwards", "accept_rate", ...}).
    """
    jax = _jax()
    jnp = jax.numpy

    input_ids = jnp.asarray(input_ids, jnp.int32)
    if input_ids.ndim != 2 or input_ids.shape[0] != 1:
        raise ValueError(f"speculative_generate is batch-1 ([1, S]); got {input_ids.shape}")
    prompt_len = input_ids.shape[1]
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    cap = min(
        target_model.config.max_position_embeddings,
        draft_model.config.max_position_embeddings,
    )
    # +gamma headroom: the last iteration may write gamma speculative rows
    # past the budget before the host truncates
    if prompt_len + max_new_tokens + gamma > cap:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) + gamma "
            f"({gamma}) exceeds the smaller cache (max_position_embeddings={cap})"
        )

    from .generation import _params_mesh, _shard_batch, _trace_ctx

    mesh = _params_mesh(target_model.params)
    if mesh is not None:
        input_ids = _shard_batch(input_ids, mesh)
    mesh_key = None if mesh is None else tuple(sorted(mesh.shape.items()))
    key = ("spec", prompt_len, gamma, mesh_key)
    runners = target_model.__dict__.setdefault("_generate_runners", {})
    # the jitted closures capture the DRAFT's apply_fn: a cache hit is only
    # valid for the same draft function (id() of a dead model can be
    # recycled, so the value itself carries the identity check)
    hit = runners.get(key)
    if hit is None or hit[2] is not draft_model.apply_fn:
        t_apply, d_apply = target_model.apply_fn, draft_model.apply_fn

        @jax.jit
        def prefill(t_params, d_params, ids):
            positions = jnp.broadcast_to(jnp.arange(prompt_len), (1, prompt_len))
            t_logits, t_cache = t_apply(t_params, ids, positions=positions, decode=True, cache=None)
            _, d_cache = d_apply(d_params, ids, positions=positions, decode=True, cache=None)
            first = jnp.argmax(t_logits[0, -1].astype(jnp.float32)).astype(jnp.int32)
            return first, t_cache, d_cache

        _core = build_spec_step(t_apply, d_apply, gamma)

        @jax.jit
        def spec_step(t_params, d_params, t_cache, d_cache, last_tok, pos):
            """One iteration at frontier ``pos`` (shared core; the batch-1
            host loop discards the logprob tail). Returns
            (tokens [gamma+1], n_emit, t_cache, d_cache)."""
            t_cache, d_cache, emit, _, n_emit = _core(
                t_params, d_params, t_cache, d_cache, last_tok, pos
            )
            return emit, n_emit, t_cache, d_cache

        runners[key] = (prefill, spec_step, d_apply)
    prefill, spec_step, _ = runners[key]

    with _trace_ctx(mesh):
        first, t_cache, d_cache = prefill(target_model.params, draft_model.params, input_ids)
    out = [int(first)]
    target_forwards = 1
    pos = prompt_len
    last = first
    accepted_total = 0
    n_steps = 0
    while len(out) < max_new_tokens and (eos_token_id is None or out[-1] != eos_token_id):
        with _trace_ctx(mesh):
            emit, n_emit, t_cache, d_cache = spec_step(
                target_model.params, draft_model.params, t_cache, d_cache, last, jnp.int32(pos)
            )
        target_forwards += 1
        n_steps += 1
        n = int(n_emit)
        toks = np.asarray(emit)[:n].tolist()
        if eos_token_id is not None and eos_token_id in toks:
            toks = toks[: toks.index(eos_token_id) + 1]
            out.extend(toks)
            break
        out.extend(toks)
        pos += n
        last = jnp.int32(out[-1])

    out = out[:max_new_tokens]
    tokens = jnp.concatenate([input_ids, jnp.asarray(out, jnp.int32)[None]], axis=1)
    if not return_stats:
        return tokens
    # stats count only USABLE tokens (post eos/budget truncation): each spec
    # step contributes one correction; everything else it kept was accepted
    accepted_usable = max(0, len(out) - 1 - n_steps)
    stats = {
        "target_forwards": target_forwards,
        "emitted": len(out),
        "tokens_per_target_forward": len(out) / target_forwards,
        "accept_rate": accepted_usable / max(1, n_steps * gamma),
    }
    return tokens, stats
