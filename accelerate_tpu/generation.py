"""Autoregressive generation with a jitted KV-cache decode loop.

The reference has no in-framework generation — its big-model-inference
benchmark (benchmarks/big_model_inference, per-token latency table in
BASELINE.md) calls ``transformers`` ``generate`` over dispatched modules.
Here decode is first-class and TPU-shaped:

* the KV cache is a fixed-size pytree (``models/llama.py``
  ``_cached_attention``) updated via ``dynamic_update_slice`` — static
  shapes end to end;
* prefill is ONE forward over the whole prompt (MXU-friendly), then the
  per-token loop is a single ``lax.scan`` inside one jit: no per-token
  dispatch, no host round-trips until the final token block returns;
* sampling (greedy / temperature / top-k) happens on-device inside the
  scan with an explicit folded key chain.

Works with any model whose ``apply_fn`` supports
``(params, ids, positions=..., decode=True, cache=...) -> (logits, cache)``
(the zoo's llama; the same contract is the extension point for others).
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def _jax():
    import jax

    return jax


def _params_mesh(params):
    """The mesh the model's params live on, if they are mesh-sharded.

    This is what makes ``generate`` multi-device (the reference's headline
    big-model story: inference.py:124-184 prepare_pippy, big_modeling.py:309
    dispatch_model): a model prepared with TP/FSDP rules — or sharded by
    hand — decodes in place, params never leave their shards, and the KV
    cache is laid out on the same mesh (ops/kv_cache.CACHE_KV_SPEC).
    """
    jax = _jax()
    for leaf in jax.tree_util.tree_leaves(params):
        s = getattr(leaf, "sharding", None)
        if isinstance(s, jax.sharding.NamedSharding) and s.mesh.size > 1:
            return s.mesh
    return None


def _shard_batch(x, mesh):
    """Lay a [B, ...] host batch out over the mesh's data-parallel axes
    (replicated if B doesn't divide them, or on meshes without those axes)."""
    jax = _jax()
    from .parallel.mesh import BATCH_AXES
    from .parallel.sharding import _prune_spec
    from jax.sharding import NamedSharding, PartitionSpec

    spec = _prune_spec(
        PartitionSpec(BATCH_AXES), getattr(x, "ndim", 1), getattr(x, "shape", (1,)), mesh, lenient=True
    )
    return jax.device_put(x, NamedSharding(mesh, spec))


def _trace_ctx(mesh):
    """Context under which the decode program is traced: pins ``mesh`` for
    the cache/activation sharding constraints inside model code."""
    import contextlib

    if mesh is None:
        return contextlib.nullcontext()
    from .parallel.sharding import mesh_context

    return mesh_context(mesh)


def _make_sampler(temperature: float, top_k: Optional[int]):
    """Greedy / temperature / top-k token sampler shared by the decoder-only
    and encoder-decoder loops."""
    jax = _jax()
    jnp = jax.numpy

    def sample(logits_1, key):
        logits_1 = logits_1.astype(jnp.float32)
        if temperature <= 0.0:
            return jnp.argmax(logits_1, axis=-1).astype(jnp.int32)
        if top_k is not None:
            kth = jax.lax.top_k(logits_1, top_k)[0][..., -1:]
            logits_1 = jnp.where(logits_1 < kth, -jnp.inf, logits_1)
        return jax.random.categorical(key, logits_1 / temperature, axis=-1).astype(jnp.int32)

    return sample


def _freeze_after_eos(nxt, done, eos_token_id):
    """EOS semantics shared by both loops: finished rows keep emitting EOS."""
    jnp = _jax().numpy
    if eos_token_id is None:
        return nxt, done
    nxt = jnp.where(done, eos_token_id, nxt)
    return nxt, done | (nxt == eos_token_id)


def _scan_new_tokens(step, carry, next_tok, max_new_tokens: int):
    """Run the per-token scan and assemble [B, max_new_tokens] including the
    already-sampled first token."""
    jax = _jax()
    jnp = jax.numpy
    if max_new_tokens > 1:
        _, rest = jax.lax.scan(step, carry, None, length=max_new_tokens - 1)
        return jnp.concatenate([next_tok[None], rest], axis=0).T
    return next_tok[:, None]


def generate(
    model,
    input_ids,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    seed: int = 0,
    eos_token_id: Optional[int] = None,
):
    """Generate ``max_new_tokens`` continuations of ``input_ids`` [B, S].

    ``temperature=0`` is greedy; otherwise softmax sampling at the given
    temperature, optionally truncated to the ``top_k`` highest logits.
    Returns int32 [B, S + max_new_tokens]. When ``eos_token_id`` is given,
    positions after a sequence's EOS are filled with EOS (the loop still
    runs to ``max_new_tokens`` — static shapes; early exit would retrace).
    """
    jax = _jax()
    jnp = jax.numpy

    apply_fn = model.apply_fn
    params = model.params
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, prompt_len = input_ids.shape

    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if max_new_tokens == 0:
        return input_ids

    max_pos = getattr(getattr(model, "config", None), "max_position_embeddings", None)
    if max_pos is not None and prompt_len + max_new_tokens > max_pos:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"model's cache size (max_position_embeddings={max_pos}); "
            f"dynamic_update_slice would silently wrap and corrupt the output"
        )

    # mesh-sharded decode: if the params live on a multi-device mesh, the
    # batch is laid out over its data axes and the decode program is traced
    # with that mesh pinned (KV cache sharded over tensor/data inside)
    mesh = _params_mesh(params)
    if mesh is not None:
        input_ids = _shard_batch(input_ids, mesh)

    # the jitted runner is cached on the model: a fresh jit closure per
    # call would retrace + recompile every generate() (and defeat
    # per_token_latency's warm-up)
    mesh_key = None if mesh is None else tuple(sorted(mesh.shape.items()))
    cache_key = (b, prompt_len, max_new_tokens, float(temperature), top_k, eos_token_id, mesh_key)
    runners = model.__dict__.setdefault("_generate_runners", {})
    if cache_key in runners:
        # still under the mesh context: jit may retrace on new avals (e.g.
        # params re-cast), and a retrace without the mesh pinned would drop
        # the KV-cache sharding constraints
        with _trace_ctx(mesh):
            return runners[cache_key](params, input_ids, jax.random.key(seed))

    @jax.jit
    def run(params, input_ids, key):
        # prefill: one big forward primes the cache and yields the first
        # next-token logits
        positions = jnp.broadcast_to(jnp.arange(prompt_len), (b, prompt_len))
        logits, cache = apply_fn(params, input_ids, positions=positions, decode=True, cache=None)

        sample = _make_sampler(temperature, top_k)
        key, sub = jax.random.split(key)
        next_tok = sample(logits[:, -1], sub)
        done = jnp.zeros((b,), bool) if eos_token_id is None else next_tok == eos_token_id

        def step(carry, _):
            cache, tok, pos, key, done = carry
            positions = jnp.broadcast_to(pos[None, None], (b, 1))
            logits, cache = apply_fn(params, tok[:, None], positions=positions, decode=True, cache=cache)
            key, sub = jax.random.split(key)
            nxt, done = _freeze_after_eos(sample(logits[:, -1], sub), done, eos_token_id)
            return (cache, nxt, pos + 1, key, done), nxt

        carry = (cache, next_tok, jnp.int32(prompt_len), key, done)
        new_tokens = _scan_new_tokens(step, carry, next_tok, max_new_tokens)
        return jnp.concatenate([input_ids, new_tokens], axis=1)

    with _trace_ctx(mesh):
        out = run(params, input_ids, jax.random.key(seed))
    runners[cache_key] = run  # register only after a successful first trace
    return out


def generate_seq2seq(
    model,
    input_ids,
    max_new_tokens: int = 32,
    decoder_start_token_id: int = 0,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    seed: int = 0,
    eos_token_id: Optional[int] = None,
    attention_mask=None,
):
    """Encoder-decoder generation (T5 contract): encode once, then a jitted
    ``lax.scan`` decode loop against the decoder KV cache — the encoder
    output persists in the cache, so per-token steps never touch it.

    ``apply_fn(params, input_ids, decoder_input_ids, attention_mask=...,
    decode=True, cache=...) -> (logits, cache)``. Returns int32
    ``[B, 1 + max_new_tokens]`` starting with ``decoder_start_token_id``.
    """
    jax = _jax()
    jnp = jax.numpy

    apply_fn = model.apply_fn
    params = model.params
    # token ids for text encoders; float features (e.g. log-mels) pass as-is
    input_ids = jnp.asarray(input_ids)
    if jnp.issubdtype(input_ids.dtype, jnp.integer):
        input_ids = input_ids.astype(jnp.int32)
    b, src_len = input_ids.shape[:2]
    if attention_mask is None:
        attention_mask = jnp.ones((b, src_len), bool)

    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    start = jnp.full((b, 1), decoder_start_token_id, jnp.int32)
    if max_new_tokens == 0:
        return start

    # exactly max_new_tokens cache slots are written (start token at 0, then
    # the scan's max_new_tokens - 1 steps; the final sample is never cached)
    max_dec = getattr(getattr(model, "config", None), "max_decode_len", None)
    if max_dec is not None and max_new_tokens > max_dec:
        raise ValueError(
            f"max_new_tokens ({max_new_tokens}) exceeds the decoder cache "
            f"(max_decode_len={max_dec})"
        )

    mesh = _params_mesh(params)
    if mesh is not None:
        input_ids = _shard_batch(input_ids, mesh)
        attention_mask = _shard_batch(attention_mask, mesh)

    mesh_key = None if mesh is None else tuple(sorted(mesh.shape.items()))
    cache_key = ("s2s", b, src_len, max_new_tokens, decoder_start_token_id,
                 float(temperature), top_k, eos_token_id, mesh_key)
    runners = model.__dict__.setdefault("_generate_runners", {})
    if cache_key in runners:
        with _trace_ctx(mesh):
            return runners[cache_key](params, input_ids, attention_mask, jax.random.key(seed))

    @jax.jit
    def run(params, input_ids, attention_mask, key):
        # prefill: encoder + first decoder step on the start token
        logits, cache = apply_fn(
            params, input_ids, start, attention_mask=attention_mask, decode=True, cache=None
        )

        sample = _make_sampler(temperature, top_k)
        key, sub = jax.random.split(key)
        next_tok = sample(logits[:, -1], sub)
        done = jnp.zeros((b,), bool) if eos_token_id is None else next_tok == eos_token_id

        def step(carry, _):
            cache, tok, key, done = carry
            logits, cache = apply_fn(params, input_ids, tok[:, None], decode=True, cache=cache)
            key, sub = jax.random.split(key)
            nxt, done = _freeze_after_eos(sample(logits[:, -1], sub), done, eos_token_id)
            return (cache, nxt, key, done), nxt

        carry = (cache, next_tok, key, done)
        new_tokens = _scan_new_tokens(step, carry, next_tok, max_new_tokens)
        return jnp.concatenate([start, new_tokens], axis=1)

    with _trace_ctx(mesh):
        out = run(params, input_ids, attention_mask, jax.random.key(seed))
    runners[cache_key] = run  # register only after a successful first trace
    return out


def beam_search(
    model,
    input_ids,
    max_new_tokens: int = 32,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    eos_token_id: Optional[int] = None,
    return_scores: bool = False,
):
    """Beam-search decode of ``input_ids`` [B, S] (the remaining decode
    mode of the transformers ``generate`` surface; reference delegates it).

    One jitted program: prefill → expand the KV cache to ``num_beams``
    rows per batch element → ``lax.scan`` steps that (a) score every
    (beam, token) continuation, (b) keep the top ``num_beams`` per batch,
    and (c) REORDER the cache rows along the chosen beams. EOS beams are
    frozen (score fixed, forced EOS continuation); the returned sequence
    per batch element maximises ``score / len(new_tokens)**length_penalty``.
    Returns int32 [B, S + max_new_tokens] (plus [B] normalised scores when
    ``return_scores``).
    """
    jax = _jax()
    jnp = jax.numpy

    apply_fn = model.apply_fn
    params = model.params
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, prompt_len = input_ids.shape
    k = num_beams
    if k < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    max_pos = getattr(getattr(model, "config", None), "max_position_embeddings", None)
    if max_pos is not None and prompt_len + max_new_tokens > max_pos:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"model's cache size (max_position_embeddings={max_pos})"
        )

    mesh = _params_mesh(params)
    if mesh is not None:
        input_ids = _shard_batch(input_ids, mesh)

    cache_key = ("beam", b, prompt_len, max_new_tokens, k, float(length_penalty),
                 eos_token_id, None if mesh is None else tuple(sorted(mesh.shape.items())))
    runners = model.__dict__.setdefault("_generate_runners", {})
    if cache_key in runners:
        with _trace_ctx(mesh):
            out = runners[cache_key](params, input_ids)
            return out if return_scores else out[0]

    NEG = jnp.float32(-1e9)

    @jax.jit
    def run(params, input_ids):
        positions = jnp.broadcast_to(jnp.arange(prompt_len), (b, prompt_len))
        logits, cache = apply_fn(params, input_ids, positions=positions, decode=True, cache=None)
        logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)  # [B, V]
        vocab = logp0.shape[-1]

        # distinct first tokens seed the beams. Cache k/v buffers are
        # [..., B, max_len, H, D] (a leading layer dim when scanned), so the
        # batch axis is ndim-4; scalar index leaves have no batch dim.
        scores, tok0 = jax.lax.top_k(logp0, k)  # [B, K]

        def batch_repeat(l):
            return jnp.repeat(l, k, axis=l.ndim - 4) if l.ndim >= 4 else l

        def batch_gather(l, rows):
            return jnp.take(l, rows, axis=l.ndim - 4) if l.ndim >= 4 else l

        cache = jax.tree.map(batch_repeat, cache)  # [.., B*K, ...]

        done = (tok0 == eos_token_id) if eos_token_id is not None else jnp.zeros((b, k), bool)
        lengths = jnp.ones((b, k), jnp.int32)
        tokens = jnp.zeros((b, k, max_new_tokens), jnp.int32).at[:, :, 0].set(tok0)

        def step(carry, t):
            cache, last, scores, done, lengths, tokens = carry
            # ``last`` was emitted at scan step t-1 and occupies sequence
            # position prompt_len + t - 1
            positions = jnp.broadcast_to(prompt_len + t - 1, (b * k, 1))
            logits, cache = apply_fn(
                params, last.reshape(b * k, 1), positions=positions, decode=True, cache=cache
            )
            logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1).reshape(b, k, vocab)
            # live beams extend by any token; done beams may only "extend"
            # by EOS at unchanged score (frozen)
            cand = scores[:, :, None] + logp
            if eos_token_id is not None:
                frozen = jnp.full((b, k, vocab), NEG).at[:, :, eos_token_id].set(0.0) + scores[:, :, None]
                cand = jnp.where(done[:, :, None], frozen, cand)
            flat = cand.reshape(b, k * vocab)
            scores, idx = jax.lax.top_k(flat, k)  # [B, K]
            beam_idx = idx // vocab  # [B, K] source beam
            tok = (idx % vocab).astype(jnp.int32)

            batch_arange = jnp.arange(b)[:, None]
            rows = (batch_arange * k + beam_idx).reshape(-1)  # [B*K] cache row gather
            cache = jax.tree.map(lambda l: batch_gather(l, rows), cache)
            done = jnp.take_along_axis(done, beam_idx, axis=1)
            lengths = jnp.take_along_axis(lengths, beam_idx, axis=1)
            tokens = jnp.take_along_axis(tokens, beam_idx[:, :, None], axis=1)

            lengths = lengths + (~done).astype(jnp.int32)
            if eos_token_id is not None:
                done = done | (tok == eos_token_id)
            tokens = tokens.at[:, :, t].set(tok)
            return (cache, tok, scores, done, lengths, tokens), None

        if max_new_tokens > 1:
            carry = (cache, tok0, scores, done, lengths, tokens)
            (cache, _, scores, done, lengths, tokens), _ = jax.lax.scan(
                step, carry, jnp.arange(1, max_new_tokens)
            )

        norm = scores / (lengths.astype(jnp.float32) ** length_penalty)
        best = jnp.argmax(norm, axis=1)  # [B]
        best_tokens = jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0]  # [B, T]
        out = jnp.concatenate([input_ids, best_tokens], axis=1)
        return out, jnp.take_along_axis(norm, best[:, None], axis=1)[:, 0]

    with _trace_ctx(mesh):
        out = run(params, input_ids)
    runners[cache_key] = run
    return out if return_scores else out[0]


def per_token_latency(model, batch_size: int = 1, prompt_len: int = 32, n_tokens: int = 16) -> float:
    """Measure steady-state per-token decode latency in seconds (the
    reference's big-model-inference metric, benchmarks README "per-token").

    Method: time one LONG decode (``16 * n_tokens`` steps) and one short
    one (``n_tokens``), difference, and divide by the step delta. Both
    runs carry identical prefill + dispatch overhead, so the difference
    isolates pure decode steps; the long run is long enough (>= 128 steps
    by default) that host jitter stays small relative to the measured
    span. (An earlier short-pair variant of this measurement was dominated
    by that jitter and over-reported quantized decode by ~7x.)
    """
    import time

    ids = np.ones((batch_size, prompt_len), np.int32)
    n_long, n_short = 16 * n_tokens, n_tokens
    # clamp to the model's KV-cache budget (generate() rejects overruns)
    max_pos = getattr(getattr(model, "config", None), "max_position_embeddings", None)
    if max_pos is not None and prompt_len + n_long > max_pos:
        n_long = max_pos - prompt_len
        n_short = max(1, n_long // 16)
        if n_long <= n_short:
            raise ValueError(
                f"cache too small to measure: prompt {prompt_len} leaves {n_long} decode steps "
                f"(max_position_embeddings={max_pos})"
            )

    def sync(out):
        import jax

        jax.block_until_ready(out)

    def timed(n):
        t0 = time.perf_counter()
        out = generate(model, ids, max_new_tokens=n)
        sync(out)
        return time.perf_counter() - t0

    # compile/warm each token count once; the jitted runner is cached on
    # the model, so the timed runs below measure pure execution
    for n in (n_long, n_short):
        sync(generate(model, ids, max_new_tokens=n))

    best = min(timed(n_long) - timed(n_short) for _ in range(2))
    if best <= 0:
        # noise swamped the signal — report the amortized whole-run cost
        # (a conservative upper bound incl. prefill)
        return timed(n_long) / n_long
    return best / (n_long - n_short)
