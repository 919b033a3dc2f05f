"""EVA: chunked linearised attention (EvaByte), in the shapes the model zoo and the serving engine run.

A sequence is cut into *chunks* of ``chunk`` positions and *windows* of ``window`` positions (a whole
number of chunks), both aligned at position 0. Position ``t`` in window ``w = t // window`` attends, under
ONE softmax, to

* the exact keys and values of its own window up to itself, ``window * w <= j <= t``, and
* one *summary* a chunk of every window before it, chunks ``m < (window // chunk) * w``: a pooled key
  ``K~_m = sum_j a_j k_j``, ``a = softmax_j(scale * k_j . mu_h)`` and a pooled value ``V~_m = sum_j b_j
  v_j``, ``b = softmax_j(scale * k_j . phi_h)``, ``j`` over the chunk's positions, ``mu_h`` / ``phi_h``
  learned vectors a head (:func:`pool_chunks`). A summary enters as one column: no count term.

So what a sequence keeps is one window of rows and ``1 / chunk`` of a row for every position before it;
the window is *aligned*, not sliding: all of a window's rows die at once when its last position is
written (the window *closes*), and the summaries of its chunks become visible in the same step.

Three callers, one arithmetic (the pooling's sums and the softmaxes are float32 element-wise, the same on
every backend; the two products of the attention take the stream's type with float32 scores):

* a forward pass with no cache and a prefill that starts a cache: :func:`eva_prefill_attention`, a
  window at a time against ``[summaries of the windows before | the window's own rows, causal]`` (a causal
  mask aligned bottom-right: the flash kernel's, for a whole window on the chip);
* a step against a dense cache that exists (``generate``, the engine's dense layout): one masked
  product over ``[every summary | every row]`` (:func:`eva_cached_attention`, the warm branch);
* a decode step against the paged pool: :func:`eva_paged_attention`. A summary row has a K/V row's
  shape, so summaries live sixteen to a page of the layer's own pool under a second per-slot table
  (``summary_table``), a chunk is pooled when its last row is written, and the rows a step reads are
  whole summary pages followed by the present window's pages: ONE gathered table a slot and the paged
  decode kernel as it is.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# queries of a prefill window multiplied together on the plain path: bounds the float32 scores at [H, Q_BLOCK, summaries + window]
Q_BLOCK = 512
# a prefill window of at least this many rows runs the flash kernel on the chip, a shorter one
# the masked product. On a v5e at EvaByte's widths (8 layers; PERF.md 6, PR 42): a bucket of 4096, two windows, 168.0 ms
# with the masked product and 117.3 with the kernel (it is the window with summaries ahead that XLA is slow at); one
# of 1024 reads 29.9 and 29.7, one of 256 11.7 and 12.3
FLASH_MIN_ROWS = 2048


def pool_chunks(k, v, mu, phi, *, chunk: int, scale: float):
    """The summaries of whole chunks. ``k``, ``v`` ``[..., T, H, D]`` with ``T`` a multiple of ``chunk``
    (rotated keys, as the cache holds them), ``mu``, ``phi`` ``[H, D]``; returns ``(K~, V~)`` ``[..., T //
    chunk, H, D]`` in the inputs' types. Float32 element-wise throughout: 16 rows a chunk are no matmul."""
    *lead, t, h, d = k.shape
    if t % chunk:
        raise ValueError(f"{t} rows are no whole number of chunks of {chunk}")
    kc = k.astype(jnp.float32).reshape(*lead, t // chunk, chunk, h, d)
    vc = v.astype(jnp.float32).reshape(*lead, t // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.sum(kc * mu.astype(jnp.float32), axis=-1) * scale, axis=-2)  # [..., M, chunk, H]
    b = jax.nn.softmax(jnp.sum(kc * phi.astype(jnp.float32), axis=-1) * scale, axis=-2)
    return (jnp.sum(a[..., None] * kc, axis=-3).astype(k.dtype), jnp.sum(b[..., None] * vc, axis=-3).astype(v.dtype))


def _attend(q, k, v, visible, scale):
    """``q`` ``[B, Q, H, D]`` against ``k``, ``v`` ``[B, K, H, D]`` under ``visible`` ``[Q, K]`` (or ``[B, Q, K]``)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    mask = visible[None, None] if visible.ndim == 2 else visible[:, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def eva_prefill_attention(q, k, v, mu, phi, *, window: int, chunk: int, scale: float):
    """EVA over a whole sequence that starts at position 0. ``q``, ``k``, ``v`` ``[B, S, H, D]`` (rotated);
    returns ``(out [B, S, H, D], K~, V~ [B, S // chunk, H, D])``. Window ``w`` attends ``[the summaries of
    windows < w | its own rows, causal]`` under one softmax.

    With the summaries ahead of the rows that is a causal mask aligned bottom-right (query ``i`` sees keys
    ``0 .. n_sum + i``), which the flash kernel has: on the chip a window of ``FLASH_MIN_ROWS`` rows or more runs it
    and forms no score matrix (off the chip under ``paged_kv.FORCE_KERNEL_INTERPRET``, interpreted). Else the masked
    product, ``Q_BLOCK`` queries at a time."""
    from . import paged_kv

    b, s, h, d = q.shape
    on_tpu = jax.default_backend() == "tpu"
    whole = s - s % chunk
    with jax.named_scope("eva.pool"):
        sk, sv = pool_chunks(k[:, :whole], v[:, :whole], mu, phi, chunk=chunk, scale=scale)
    per = window // chunk
    outs = []
    with jax.named_scope("eva.prefill"):
        for w in range(-(-s // window)):
            lo, hi = w * window, min((w + 1) * window, s)
            n, n_sum = hi - lo, per * w
            kw = jnp.concatenate([sk[:, :n_sum], k[:, lo:hi]], axis=1)
            vw = jnp.concatenate([sv[:, :n_sum], v[:, lo:hi]], axis=1)
            if n >= FLASH_MIN_ROWS and (on_tpu or paged_kv.FORCE_KERNEL_INTERPRET):
                from .attention import sharded_pallas_attention

                outs.append(sharded_pallas_attention(q[:, lo:hi], kw, vw, causal=True, scale=scale, interpret=not on_tpu))
                continue
            col = jnp.arange(n_sum + n)[None, :]
            qb = Q_BLOCK if n % Q_BLOCK == 0 else n

            def block(args, kw=kw, vw=vw, col=col, n_sum=n_sum, qb=qb):
                qi, first = args  # [B, qb, H, D], the block's first row in the window
                row = first + jnp.arange(qb)[:, None]
                return _attend(qi, kw, vw, (col < n_sum) | (col - n_sum <= row), scale)

            blocks = q[:, lo:hi].reshape(b, n // qb, qb, h, d).swapaxes(0, 1)
            out = jax.lax.map(block, (blocks, jnp.arange(n // qb) * qb))
            outs.append(out.swapaxes(0, 1).reshape(b, n, h, d))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0], sk, sv


def eva_cached_attention(module, q, k, v, mu, phi, max_len: int, *, window: int, chunk: int, scale=None):
    """Incremental EVA against the cache of ``module`` (a flax module that owns the ``cache`` variables).

    ``q``, ``k``, ``v`` ``[B, S_new, H, D]``, rotated at their absolute positions. Under the serving
    engine's paged layout this is :func:`eva_paged_attention`. Else the cache is dense: ``key`` / ``value``
    ``[B, max_len, H, D]``, ``summary_key`` / ``summary_value`` ``[B, max_len // chunk, H, D]`` and a scalar
    ``index``. A call that *starts* the cache (a prefill: nothing before it) runs window by window
    (:func:`eva_prefill_attention`) and never forms a ``[S_new, max_len]`` score matrix; a call against a
    cache that exists writes its rows, pools every chunk anew from the rows held (a dense cache is the
    plain path: ``max_len`` rows a step) and attends under the mask of the two rules, whatever ``S_new``."""
    from . import paged_kv

    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    if window % chunk:
        raise ValueError(f"an EVA window of {window} positions is no whole number of chunks of {chunk}")
    pcfg = paged_kv.active_paged_config()
    if pcfg is not None:
        return eva_paged_attention(module, q, k, v, mu, phi, max_len, window=window, chunk=chunk, scale=scale, cfg=pcfg)
    b, s_new, h, _ = k.shape
    if max_len % chunk:
        raise ValueError(f"a cache of {max_len} rows is no whole number of chunks of {chunk}")
    starts = not module.has_variable("cache", "key")
    ck = module.variable("cache", "key", jnp.zeros, (b, max_len, h, d), k.dtype)
    cv = module.variable("cache", "value", jnp.zeros, (b, max_len, h, d), v.dtype)
    sk = module.variable("cache", "summary_key", jnp.zeros, (b, max_len // chunk, h, d), k.dtype)
    sv = module.variable("cache", "summary_value", jnp.zeros, (b, max_len // chunk, h, d), v.dtype)
    idx = module.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
    cur = idx.value
    ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, cur, 0, 0))
    cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, cur, 0, 0))
    idx.value = cur + s_new
    if starts:
        out, pooled_k, pooled_v = eva_prefill_attention(q, k, v, mu, phi, window=window, chunk=chunk, scale=scale)
        sk.value = jax.lax.dynamic_update_slice(sk.value, pooled_k, (0, 0, 0, 0))
        sv.value = jax.lax.dynamic_update_slice(sv.value, pooled_v, (0, 0, 0, 0))
        return out
    with jax.named_scope("eva.pool"):
        sk.value, sv.value = pool_chunks(ck.value, cv.value, mu, phi, chunk=chunk, scale=scale)
    t = (cur + jnp.arange(s_new))[:, None]  # [S_new, 1]
    first = (t // window) * window
    row, m = jnp.arange(max_len)[None, :], jnp.arange(max_len // chunk)[None, :]
    visible = jnp.concatenate([m < first // chunk, (row >= first) & (row <= t)], axis=1)
    keys = jnp.concatenate([sk.value, ck.value], axis=1)
    values = jnp.concatenate([sv.value, cv.value], axis=1)
    return _attend(q, keys, values, visible, scale)


def check_paged_sizes(block_size: int, window: int, chunk: int) -> None:
    """The paged layout's two conditions: a page is a chunk, and a window's summaries are whole pages."""
    if block_size != chunk or window % (chunk * block_size):
        raise NotImplementedError(
            f"EVA under the paged layout keeps a chunk to a page and a window's summaries in whole pages: "
            f"paged_block_size {block_size}, chunk {chunk}, window {window} (want block == chunk and window % (chunk * block) == 0)")


def gathered_width(max_len: int, block_size: int, window: int, chunk: int) -> int:
    """Entries of the table a decode step walks: the summary pages of every window a sequence of ``max_len``
    positions can have closed, then one window's pages."""
    return (window // chunk // block_size) * ((max_len - 1) // window) + window // block_size


def summary_pages(max_len: int, block_size: int, chunk: int) -> int:
    """Entries of ``summary_table``: pages of ``block_size`` summaries over ``max_len // chunk`` chunks."""
    return -(-(max_len // chunk) // block_size)


def gather_table(block_table, summary_table, cur, *, block_size: int, window: int, chunk: int, max_len: int):
    """The table and frontier a decode step hands the paged kernel. For a slot at position ``t`` in window
    ``w``: the ``8 w`` (``window // chunk // block_size`` a window) whole summary pages of the windows
    before, then the present window's pages ``block_table[128 w ...]``; the frontier, in rows of that
    table, is ``128 w + t % window``. ``[B, gathered_width]`` int32 and ``[B]`` int32."""
    per_w, sum_w = window // block_size, window // chunk // block_size
    w = jnp.minimum(cur // window, (max_len - 1) // window)
    n_sum = (sum_w * w)[:, None]
    i = jnp.arange(gathered_width(max_len, block_size, window, chunk))[None, :]
    exact = jnp.clip(per_w * w[:, None] + i - n_sum, 0, block_table.shape[1] - 1)
    summaries = summary_table[:, jnp.minimum(i[0], summary_table.shape[1] - 1)]
    table = jnp.where(i < n_sum, summaries, jnp.take_along_axis(block_table, exact, axis=1))
    return table, n_sum[:, 0] * block_size + cur - window * w


def eva_paged_attention(module, q, k, v, mu, phi, max_len: int, *, window: int, chunk: int, scale: float, cfg):
    """One decode step of EVA for every slot against the paged pool.

    Declares (a layer) ``key_pool`` / ``value_pool`` ``[NB, bs, H, D]``, ``block_table`` ``[B, MB]``,
    ``summary_table`` ``[B, max_len // chunk // bs]`` and a per-row ``index`` ``[B]``. A page is a chunk
    (``bs == chunk``) and a window's summaries are whole pages (``window % (chunk * bs) == 0``), so:

    1. the token's row is stored at its slot's frontier, as in ``paged_cached_attention``;
    2. a slot whose row completes a chunk (``t % chunk == chunk - 1``) has that page read back and pooled,
       and the summary written to row ``(t // chunk) % bs`` of page ``summary_table[t // (chunk * bs)]`` (any
       other slot's write lands in the trash sink). The row is there long before its window closes;
    3. it becomes visible only through :func:`gather_table`, so a window's close moves no data: the step
       at which ``t // window`` grows walks eight pages more of summaries and starts its exact rows at a new
       table index, in the middle of a tick if need be.

    The walk is ``paged_decode_attention``'s, as it is (XLA's gather off the chip): the rows it may read are
    exactly the live ones, so it needs no mask of its own."""
    from . import paged_kv

    b, s_new, h, d = k.shape
    if s_new != 1:
        raise ValueError(
            f"paged attention is decode-only (S_new == 1, got {s_new}); prefill runs the dense path and is pasted into the pool")
    bs_, nb = cfg.block_size, cfg.num_blocks
    check_paged_sizes(bs_, window, chunk)
    mb = -(-max_len // bs_)
    kp = module.variable("cache", "key_pool", jnp.zeros, (nb, bs_, h, d), k.dtype)
    vp = module.variable("cache", "value_pool", jnp.zeros, (nb, bs_, h, d), v.dtype)
    bt = module.variable("cache", "block_table", jnp.zeros, (b, mb), jnp.int32)
    st = module.variable("cache", "summary_table", jnp.zeros, (b, summary_pages(max_len, bs_, chunk)), jnp.int32)
    idx = module.variable("cache", "index", jnp.zeros, (b,), jnp.int32)
    cur, rows = idx.value, jnp.arange(b)
    dest = bt.value[rows, jnp.minimum(cur // bs_, mb - 1)]  # overshoot clamp, as in paged_cached_attention
    key_pool = kp.value.at[dest, cur % bs_].set(k[:, 0])
    value_pool = vp.value.at[dest, cur % bs_].set(v[:, 0])
    with jax.named_scope("eva.pool"):
        pooled_k, pooled_v = pool_chunks(key_pool[dest], value_pool[dest], mu, phi, chunk=chunk, scale=scale)
        m = cur // chunk
        done = (cur % chunk == chunk - 1) & (dest != 0)
        page = jnp.where(done, st.value[rows, jnp.minimum(m // bs_, st.value.shape[1] - 1)], 0)
        key_pool = key_pool.at[page, m % bs_].set(pooled_k[:, 0])
        value_pool = value_pool.at[page, m % bs_].set(pooled_v[:, 0])
    kp.value, vp.value = key_pool, value_pool
    idx.value = cur + 1
    with jax.named_scope("eva.table"):
        table, frontier = gather_table(
            bt.value, st.value, cur, block_size=bs_, window=window, chunk=chunk, max_len=max_len)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or paged_kv.FORCE_KERNEL_INTERPRET:
        import functools

        from .pallas_paged_attention import paged_decode_attention

        run = paged_kv._kernel_runner(functools.partial(paged_decode_attention, scale=scale, interpret=not on_tpu), h, h)
        if run is not None:
            # a slot that stores into the sink is idle, or finished and overshooting: no keys, no copy and no fold, as
            # in paged_cached_attention
            return run(q[:, 0], key_pool, value_pool, table, jnp.where(dest == 0, paged_kv.NO_KEYS, frontier))[:, None]
    return paged_kv.paged_gather_attention(q, key_pool, value_pool, table, frontier, scale=scale)
