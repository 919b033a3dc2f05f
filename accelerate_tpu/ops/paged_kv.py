"""Paged KV cache: a shared block pool + per-sequence block tables.

The dense serving cache reserves ``max_len`` rows per slot, so HBM caps
the slot count at ``pool_bytes = slots x max_len`` even when most
requests are short. Paging (the vLLM design, shaped for XLA's static
shapes) allocates cache in fixed-size *blocks* from one shared pool:

* ``key_pool`` / ``value_pool``: ``[num_blocks, block_size, H_kv, D]``
  per layer (one ``[L, ...]`` stack on the model under a layer scan) — the
  only large buffers, sized by *expected total tokens in flight*, not
  ``slots x max_len``;
* or, for latent attention (MLA), ONE ``latent_pool``: ``[num_blocks, W,
  block_size]`` per layer, a column the compressed ``[c_kv ; k_rope]`` of
  a token (``W = kv_lora_rank + qk_rope_head_dim``), shared by every
  head: the decode step reads it as keys (all ``W`` values) and as
  values (the first ``kv_lora_rank``). A page is stored transposed (tokens
  along the minor axis) because ``W`` is no multiple of the 128 lanes: the
  TPU compiler gives a ``[.., block_size, 576]`` array that layout anyway
  and then re-lays the whole pool out around every program that wants
  rows. Read through :func:`paged_latent_attention` and the kernel in
  :mod:`.pallas_latent_attention`, which walks a slot's live pages as the
  K/V kernel does (one grid step a slot, two pages a chunk copied into the
  lanes of one of two VMEM buffers, one product a chunk for the scores and
  one for the values; a slot that stores into the sink is handed "no
  keys", :data:`NO_KEYS`, and costs no copy and no fold); everything
  below (tables, the sink, prefix aliasing, donation) holds for it as for
  K/V, the carried stack of a layer scan apart: latent layers are
  unrolled, a pool each;
* beside the pools, for a layer with a recurrent state (Mamba, a gated
  short convolution), NO pool: its state a sequence is fixed in size, so
  the layer keeps ``ssm_state`` / ``conv_state`` leaves (a convolution layer
  ``conv_state`` alone) with one row a slot (:data:`STATE_LEAVES`), in this
  layout as in the dense one; a hybrid model's cache tree holds both kinds,
  the K/V of its attention layers paged, a pool a layer;
* ``block_table``: ``[B, max_blocks]`` int32 per row — position ``p`` of
  row ``b`` lives at ``pool[table[b, p // bs], p % bs]``;
* block 0 is a reserved **trash sink**: padded table entries and the
  post-retirement overshoot writes of a static decode tick land there,
  so a retired slot can never corrupt a block that was freed and
  reallocated to another request (see ``ServingEngine._retire``, which
  also re-points the whole retired row at the sink);
* shared prompt prefixes alias their *full* blocks into many tables
  (refcounted host-side) — prefix reuse without copying cache rows.

The pool is ONE device buffer for the engine's life. Every program that
takes the paged cache (the decode tick, :func:`paste_row`,
:func:`paste_blocks`, :func:`clear_slots`, :func:`set_table_row`) is jitted
with the cache donated, writes into it in place and hands the same buffer
back; inside the tick a scanned layer stack carries the pools of all its
layers through the layer loop as one ``[L, NB, bs, H_kv, D]`` stack per K
and V (:func:`declare_pool_stack`, :func:`layer_view`) instead of scanning
over them, so storing a token's rows never moves the pool. For code that
holds a cache reference this means: the array passed to such a program is
deleted by the call — rebind to what it returns (``cache = f(cache, ...)``)
and never read the old value again.

Everything stays static-shape. On TPU the decode step dispatches to the
Pallas kernel in :mod:`.pallas_paged_attention` (a latent pool's to
:mod:`.pallas_latent_attention`; the walk both make is :mod:`.paged_walk`),
which leaves the pool in HBM and walks each row's LIVE pages only (first
page of the band to the frontier's, read from the scalar-prefetched table
and frontier): async copies a chunk of pages at a time into two VMEM
buffers, one product a chunk; reserved and pad table entries are never visited,
and neither is any page of a slot in which nobody decodes (:data:`NO_KEYS`)
(under a ``shard_map`` over ``tensor`` when the pool is TP-sharded — a
``pallas_call`` can't be auto-partitioned). The XLA fallback (CPU, or
head counts the tensor axis can't split) gathers ``pool[table]`` into a
contiguous ``[B, L, H_kv, D]`` copy — dense-equivalent read bytes plus
the gather write. Either way paging wins pool *capacity* (more
concurrent slots per GB); the kernel also wins decode traffic.

The reference has no serving/paged-cache analogue (it delegates
generation entirely — SURVEY §2.2/§7); this is parity-plus. The paged
branch is selected at *trace time* by :func:`paged_mode`, so the model
zoo's ``cached_attention`` call sites need no changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    block_size: int
    num_blocks: int  # total pool blocks INCLUDING the reserved trash block 0
    # A pool and a table a KIND of layer (a model whose layers are some windowed and some not): with
    # ``window_ring`` set, a layer under a sliding window keeps pools of ``window_blocks`` blocks (its own
    # trash sink among them) and, in place of ``block_table``, a ``window_table`` ``[B, window_ring]`` that a
    # slot uses as a ring: position ``p`` lives at entry ``(p // block_size) % window_ring``
    # (:func:`ring_entries` of the window: no more pages than the band spans, and one to spare). A layer
    # without a window keeps ``num_blocks`` and ``block_table``. None: every layer alike, as it was.
    window_ring: Optional[int] = None
    window_blocks: Optional[int] = None


def ring_entries(window: int, block_size: int, max_len: int) -> int:
    """Entries of a slot's ring under a band of ``window`` keys: the band of a frontier at offset ``r`` of
    its page reaches back ``window - 1`` keys, ``ceil((window - 1) / block_size) + 1`` pages at most (512 keys in
    pages of 16: 33, at every ``r`` but the page's last), and one entry is spare, so that what a step is about
    to write never shares an entry with what a step still reads. Never more than the whole context's."""
    return min(-(-(window - 1) // block_size) + 2, -(-max_len // block_size))


_ACTIVE: Optional[PagedConfig] = None

# Route the off-TPU paged path through the Pallas kernel in interpret
# mode instead of the XLA gather — CI's hook for exercising the exact
# kernel-in-engine composition TPU serving runs, without a chip.
FORCE_KERNEL_INTERPRET = False

# The frontier the decode kernels are handed for a row that stores this step's token into the sink: below
# zero, which the walk (:mod:`.paged_walk`) reads as "no keys". The XLA gather paths are given the row's
# own frontier.
NO_KEYS = -1


def active_paged_config() -> Optional[PagedConfig]:
    return _ACTIVE


@contextlib.contextmanager
def paged_mode(cfg: PagedConfig):
    """Trace-time switch: while active, ``cached_attention`` declares and
    updates the paged cache layout instead of dense ``[B, max_len]``
    buffers. Only the *tracing* of a program needs the context — the
    serving engine re-enters it around every (lazily jitted) tick call,
    which is free on cache hits and lets jit re-trace when GSPMD
    propagates new shardings onto the pool. Do NOT eagerly
    ``.lower().compile()`` under this context: that pins the input
    shardings seen at construction and rejects the runtime arrays on
    data-sharded meshes (see tests/test_serving_paged.py)."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, cfg
    try:
        yield
    finally:
        _ACTIVE = prev


@dataclasses.dataclass
class _LayerView:
    """One layer's share of pools that a layer scan carries as loop
    state: the flattened stacks ``[L * NB, bs, H_kv, D]`` and the layer's
    first block in them. Lives for the trace of one scan-body call."""

    key_pool: jax.Array
    value_pool: jax.Array
    base: jax.Array  # layer * NB


_LAYER_VIEW: Optional[_LayerView] = None

# a pool leaf -> the dense row-cache leaf it holds the rows of
POOL_ROWS = {"key_pool": "key", "value_pool": "value", "latent_pool": "latent"}
# a pool leaf -> the dense row-cache leaf of chunk summaries that it also holds, a page of them under
# ``summary_table`` (EVA, :mod:`.eva_attention`: a summary is a row of the pool's own shape)
SUMMARY_ROWS = {"key_pool": "summary_key", "value_pool": "summary_value"}

# Leaves with a slot axis and no row axis: the recurrent state of a state-space layer (``ssm_state``
# ``[B, d_state, d_inner]``, ``conv_state`` ``[B, (d_conv - 1) * d_inner]``) and of a gated short
# convolution (``conv_state`` ``[B, (conv_L_cache - 1) * hidden]`` alone), one row a slot whatever the
# sequence's length, the same leaf in the dense row cache (``B`` 1) and in the paged cache (``B`` slots).
# No pages: :func:`paste_row` writes a prefill's state over the slot's, :func:`paste_blocks` passes it
# (a shared prefix shares blocks, not state: each request carries a copy of the prefix's), and
# :func:`clear_slot` zeroes it. They are donated with the pools: the tick holds one copy.
STATE_LEAVES = ("ssm_state", "conv_state")


def state_bytes(cache) -> int:
    """Bytes of the recurrent-state leaves of ``cache``: 0 for a model without state-space layers."""
    return sum(
        math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        if _path_names(path)[-1] in STATE_LEAVES
    )


def declare_pool_stack(module, num_layers: int, kv_heads: int, head_dim: int, dtype):
    """The pools of ``num_layers`` scanned layers as ONE pair of ``cache``
    variables ``[L, NB, bs, H_kv, D]`` on ``module``, the module that owns
    the layer scan — or None when no paged layout is active. (A head under
    the lane width is folded: :func:`pool_lane_fold`.)

    Scanning over the ``cache`` collection would hand each layer a fresh
    slice of the stack and collect the updated slices into a second stack:
    two passes over the whole pool per token to store one row per slot. A
    scan *carries* the stack instead (a carried value cannot be born
    inside the body, hence this declaration outside it) and every layer
    writes and reads its own ``NB`` blocks of it in place, through
    :func:`layer_view`."""
    cfg = _ACTIVE
    if cfg is None:
        return None
    fold = pool_lane_fold(kv_heads, head_dim)
    shape = (num_layers, cfg.num_blocks, cfg.block_size, kv_heads // fold, fold * head_dim)
    return (
        module.variable("cache", "key_pool", jnp.zeros, shape, dtype),
        module.variable("cache", "value_pool", jnp.zeros, shape, dtype),
    )


@contextlib.contextmanager
def layer_view(key_pool, value_pool, layer):
    """Inside a scan body: route this layer's :func:`paged_cached_attention`
    to the carried stacks ``[L, NB, bs, H_kv, D]`` instead of ``cache``
    variables of its own. Yields the view; after the layer ran, its
    ``key_pool`` / ``value_pool`` are the updated stacks to carry on.

    The stack is viewed as ``[L * NB, bs, H_kv, D]`` (a bitcast) and the
    layer addresses block ``i`` as ``layer * NB + i``, so the pool keeps
    rank 4 for ``POOL_KV_SPEC`` and the kernel, and each layer keeps a
    trash sink of its own (its block 0)."""
    global _LAYER_VIEW
    shape = key_pool.shape
    flat = (shape[0] * shape[1], *shape[2:])
    view = _LayerView(key_pool.reshape(flat), value_pool.reshape(flat), layer * shape[1])
    prev, _LAYER_VIEW = _LAYER_VIEW, view
    try:
        yield view
    finally:
        _LAYER_VIEW = prev
    view.key_pool = view.key_pool.reshape(shape)
    view.value_pool = view.value_pool.reshape(shape)


# Pool layout on a mesh: heads over ``tensor`` (same TP decode layout as
# the dense CACHE_KV_SPEC); the block axis is NOT batch — the pool is
# shared by every row — so it stays unsharded.
POOL_KV_SPEC = P(None, None, "tensor", None)


def _constrain_pool(x):
    from ..parallel.sharding import maybe_shard

    return maybe_shard(x, POOL_KV_SPEC)


LANES, SUBLANES = 128, 8  # a TPU tile of 32-bit values: its minor axis and the axis before it


def pool_lane_fold(kv_heads: int, head_dim: int) -> int:
    """How many key/value heads of one token share a row of the pool: 1, or
    ``128 // head_dim`` for a head under the lane width (LFM2's 64). A pool
    ``[NB, bs, H_kv, 64]`` has half a lane tile as its minor axis: the TPU
    compiler either pads it to 128 (twice the pool's bytes and the kernel's
    traffic, and a page slice off the tiling, which Mosaic refuses) or lays
    the block axis innermost (no page is contiguous). So such a pool is
    declared ``[NB, bs, H_kv / f, f * D]``: the same bytes in the same order
    (a token's heads are consecutive), heads ``f * i .. f * i + f - 1`` side
    by side in row ``i``. Whoever writes a token reshapes it (free);
    :func:`~accelerate_tpu.ops.pallas_paged_attention.paged_decode_attention`
    reads the folded rows as they are. Not under a tensor-parallel mesh,
    whose pool splits its head axis."""
    from .attention import active_mesh

    fold = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 else 1
    if fold == 1 or kv_heads % fold:
        return 1
    mesh = active_mesh()
    if mesh is not None:
        from ..parallel.mesh import axis_size

        if axis_size(mesh, "tensor") > 1:
            return 1
    return fold


def paged_cached_attention(
    module, q, k, v, max_len: int, scale=None, bias_fn=None, sliding_window=None, cfg: PagedConfig = None
):
    """Single-token incremental attention against the paged pool.

    Declares (per layer) ``key_pool``/``value_pool`` ``[NB, bs, H_kv, D]``
    (``[NB, bs, H_kv / f, f * D]`` for a head under the lane width:
    :func:`pool_lane_fold`), ``block_table`` ``[B, MB]`` and a PER-ROW ``index`` ``[B]`` — ragged
    row positions are native here (the dense branch's scalar frontier
    forces the serving engine to vmap row-wise; the paged tick runs one
    batched program instead). Inside a :func:`layer_view` the pools are
    the layer's blocks of the carried stack instead. Prefill always runs
    dense and is pasted into the pool by :func:`paste_row`, so only
    ``S_new == 1`` decode steps ever trace this branch.
    """
    b, s_new, h_kv, d = k.shape
    if s_new != 1:
        raise ValueError(
            f"paged attention is decode-only (S_new == 1, got {s_new}); "
            "prefill runs the dense path and is pasted into the pool"
        )
    if bias_fn is not None:
        raise NotImplementedError("paged attention does not support bias_fn (T5-style relative bias)")
    bs_, nb = cfg.block_size, cfg.num_blocks
    mb = -(-max_len // bs_)
    scale = (1.0 / math.sqrt(d)) if scale is None else scale

    fold = pool_lane_fold(h_kv, d)
    row = (h_kv // fold, fold * d)  # one token of the pool: [H_kv, D], or heads side by side in 128 lanes
    view = _LAYER_VIEW
    ring = cfg.window_ring is not None and sliding_window is not None
    if ring:
        if view is not None:
            raise NotImplementedError("a table a kind of layer is built for unrolled layers: the carried pool stack has one shape")
        return _ring_cached_attention(module, q, k, v, row, scale=scale, sliding_window=sliding_window, cfg=cfg)
    if view is None:
        kp = module.variable("cache", "key_pool", jnp.zeros, (nb, bs_, *row), k.dtype)
        vp = module.variable("cache", "value_pool", jnp.zeros, (nb, bs_, *row), v.dtype)
        key_pool, value_pool = kp.value, vp.value
    else:
        key_pool, value_pool = view.key_pool, view.value_pool
        if (key_pool.dtype, value_pool.dtype) != (k.dtype, v.dtype):
            raise TypeError(
                f"the carried pool stack is {key_pool.dtype}/{value_pool.dtype} but the layer's "
                f"keys/values are {k.dtype}/{v.dtype}: declare_pool_stack was given the wrong dtype"
            )
    bt = module.variable("cache", "block_table", jnp.zeros, (b, mb), jnp.int32)
    idx = module.variable("cache", "index", jnp.zeros, (b,), jnp.int32)

    cur = idx.value  # [B] per-row write positions
    rows = jnp.arange(b)
    # overshoot clamp: a slot that finished mid-tick keeps computing with
    # growing cur; past the table it clamps to the last entry (its own
    # reserved block or the trash sink — never another row's block)
    blk = jnp.minimum(cur // bs_, mb - 1)
    table = bt.value if view is None else bt.value + view.base  # this layer's blocks of the stack
    dest = table[rows, blk]  # [B] pool block ids
    off = cur % bs_
    key_pool = _constrain_pool(key_pool.at[dest, off].set(k[:, 0].reshape(b, *row)))
    value_pool = _constrain_pool(value_pool.at[dest, off].set(v[:, 0].reshape(b, *row)))
    if view is None:
        kp.value, vp.value = key_pool, value_pool
    else:
        view.key_pool, view.value_pool = key_pool, value_pool
    idx.value = cur + 1

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or FORCE_KERNEL_INTERPRET:
        # Pallas kernel: reads each row's live pages once, out of the pool
        # in HBM, through the scalar-prefetched table and frontier — no
        # [B, L, H_kv, D] gather materialisation (the XLA fallback below
        # writes+rereads one; ~3x the attention traffic)
        import functools

        from .pallas_paged_attention import paged_decode_attention

        fn = functools.partial(
            paged_decode_attention, sliding_window=sliding_window, scale=scale, interpret=not on_tpu
        )
        run = _kernel_runner(fn, q.shape[2], h_kv)
        if run is not None:  # None: TP mesh the heads can't split -> XLA path
            # A row that stores this token in the sink is idle, or finished and overshooting: nothing
            # reads what it attends to. It is handed a frontier below zero, "no keys": the kernel's walk
            # starts no copy and folds nothing for it, its output is zeros, and the row before it starts
            # the first copies of the next row that has keys. A live row at frontier 0 (one key) is walked.
            sink = 0 if view is None else view.base
            return run(q[:, 0], key_pool, value_pool, table, jnp.where(dest == sink, NO_KEYS, cur))[:, None]

    return paged_gather_attention(
        q, key_pool, value_pool, table, cur, scale=scale, sliding_window=sliding_window
    )


def _ring_cached_attention(module, q, k, v, row, *, scale, sliding_window, cfg: PagedConfig):
    """:func:`paged_cached_attention` for a layer under a sliding window of a cache that keeps a pool and a table
    a kind of layer (``cfg.window_ring``): pools of ``cfg.window_blocks`` blocks and a ``window_table`` ``[B,
    ring]`` used as a ring. The token's row goes to entry ``(cur // bs) % ring``, whatever ``cur``: a slot that
    finished mid-tick and overshoots goes round its own ring (or the sink's, once cleared), never into another
    slot's block, and an entry is written again only ``ring`` pages later, when the band has left what it held."""
    b = k.shape[0]
    bs_, nb, entries = cfg.block_size, cfg.window_blocks, cfg.window_ring
    kp = module.variable("cache", "key_pool", jnp.zeros, (nb, bs_, *row), k.dtype)
    vp = module.variable("cache", "value_pool", jnp.zeros, (nb, bs_, *row), v.dtype)
    wt = module.variable("cache", "window_table", jnp.zeros, (b, entries), jnp.int32)
    idx = module.variable("cache", "index", jnp.zeros, (b,), jnp.int32)
    cur, table = idx.value, wt.value
    dest = table[jnp.arange(b), (cur // bs_) % entries]
    key_pool = kp.value = _constrain_pool(kp.value.at[dest, cur % bs_].set(k[:, 0].reshape(b, *row)))
    value_pool = vp.value = _constrain_pool(vp.value.at[dest, cur % bs_].set(v[:, 0].reshape(b, *row)))
    idx.value = cur + 1
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or FORCE_KERNEL_INTERPRET:
        import functools

        from .pallas_paged_attention import paged_decode_attention

        fn = functools.partial(
            paged_decode_attention, sliding_window=sliding_window, scale=scale, interpret=not on_tpu, ring=True
        )
        run = _kernel_runner(fn, q.shape[2], k.shape[2])
        if run is not None:
            # a row that stores into the sink is idle: no keys, no copy and no fold, as in paged_cached_attention
            # (said by NO_KEYS and not by a page count: a ring clamps nothing)
            walk_to = jnp.where(dest == 0, NO_KEYS, cur)
            return run(q[:, 0], key_pool, value_pool, table, walk_to)[:, None]
    return paged_gather_attention(q, key_pool, value_pool, table, cur, scale=scale, sliding_window=sliding_window, ring=True)


def paged_gather_attention(q, key_pool, value_pool, block_table, cur, *, scale, sliding_window=None, ring=False):
    """The plain XLA paged decode step: gather each row's pages into a
    contiguous copy and attend to it. ``q`` is ``[B, 1, H, D]``, the pools
    ``[NB, bs, H_kv, D]`` (or lane-folded), ``block_table`` ``[B, MB]`` and ``cur`` the
    per-row frontier ``[B]``; returns ``[B, 1, H, D]``. What the Pallas
    kernel is checked against, and what runs where it cannot. ``ring``: the
    table is a ring (``window_table``): entry ``e`` holds the newest page
    ``p <= cur // bs`` with ``p % MB == e``."""
    b, d = q.shape[0], q.shape[-1]
    bs_ = key_pool.shape[1]
    h_kv = key_pool.shape[2] * key_pool.shape[3] // d  # a lane-folded pool holds the same rows (pool_lane_fold)
    mb = block_table.shape[1]
    # gather each row's pages: [B, MB, bs, H_kv, D] -> [B, L, H_kv, D]
    k_all = key_pool[block_table].reshape(b, mb * bs_, h_kv, d)
    v_all = value_pool[block_table].reshape(b, mb * bs_, h_kv, d)
    key_pos = jnp.arange(mb * bs_)
    if ring:
        last = (cur // bs_)[:, None]  # [B, 1]: the frontier's page; entry e holds page last - (last - e) % MB
        page = last - (last - jnp.arange(mb)[None, :]) % mb  # [B, MB], negative where the ring is not yet full
        key_pos = (page[:, :, None] * bs_ + jnp.arange(bs_)[None, None, :]).reshape(b, mb * bs_)
        live = (key_pos >= 0) & (key_pos <= cur[:, None]) & (key_pos > cur[:, None] - sliding_window)
    else:
        live = key_pos[None, :] <= cur[:, None]  # [B, L] causal frontier per row
        if sliding_window is not None:
            live &= key_pos[None, :] > cur[:, None] - sliding_window  # Mistral band

    groups = q.shape[2] // h_kv
    if groups > 1:
        # GQA: contract grouped queries against the un-repeated pool rows
        # (same traffic argument as the dense branch)
        qg = q.reshape(b, 1, h_kv, groups, d)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all).astype(jnp.float32) * scale
        mask = live[:, None, None, None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_all)
        return out.reshape(b, 1, h_kv * groups, d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_all).astype(jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(live[:, None, None, :], scores, -jnp.inf), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)


def paged_latent_attention(module, q_lat, row, max_len: int, *, value_width: int, scale: float, cfg: PagedConfig):
    """Single-token absorbed latent attention (MLA) against the paged pool.

    ``q_lat`` ``[B, 1, H, W]`` are the queries with ``W_UK`` absorbed
    (``[q_nope W_UK^T ; q_rope]``), ``row`` ``[B, 1, W]`` this token's
    latent row ``[c_kv ; k_rope]``. Declares (per layer) ``latent_pool``
    ``[NB, W, bs]``, ``block_table`` ``[B, MB]`` and a per-row ``index``
    ``[B]``, stores the row at each slot's frontier, and returns ``sum_j p_j c_kv_j`` ``[B, 1, H, value_width]``: every head
    reads the same row, as key over all ``W`` columns and as value over
    the first ``value_width``. The caller applies ``W_UV``."""
    b, s_new, width = row.shape
    if s_new != 1:
        raise ValueError(
            f"paged attention is decode-only (S_new == 1, got {s_new}); "
            "prefill runs the dense path and is pasted into the pool"
        )
    bs_, nb = cfg.block_size, cfg.num_blocks
    mb = -(-max_len // bs_)
    lp = module.variable("cache", "latent_pool", jnp.zeros, (nb, width, bs_), row.dtype)
    bt = module.variable("cache", "block_table", jnp.zeros, (b, mb), jnp.int32)
    idx = module.variable("cache", "index", jnp.zeros, (b,), jnp.int32)
    pool, table, cur = lp.value, bt.value, idx.value
    blk = jnp.minimum(cur // bs_, mb - 1)  # overshoot clamp, as in paged_cached_attention
    # A token is one column of its page. The column is written a page at a time (read the frontier
    # page of every slot, set the column, write the pages back: 2 x B pages a step) because a scatter of
    # single columns makes the TPU compiler re-lay the whole pool out. Idle slots all name the sink.
    dest = table[jnp.arange(b), blk]
    pages = pool[dest]  # [B, W, bs]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs_), 2) == (cur % bs_)[:, None, None]
    pool = lp.value = pool.at[dest].set(jnp.where(at, row[:, 0, :, None], pages))
    idx.value = cur + 1

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or FORCE_KERNEL_INTERPRET:
        import functools

        from .pallas_latent_attention import latent_paged_decode

        fn = functools.partial(latent_paged_decode, value_width=value_width, scale=scale, interpret=not on_tpu)
        run = _kernel_runner(fn, q_lat.shape[2], 1, pool_specs=(P(None, None, None),))
        if run is not None:
            # a row that stores this token in the sink is idle, or finished and overshooting: no keys, no
            # copy and no fold, zeros out, as in paged_cached_attention
            return run(q_lat[:, 0], pool, table, jnp.where(dest == 0, NO_KEYS, cur))[:, None]
    return paged_latent_gather_attention(q_lat, pool, table, cur, value_width=value_width, scale=scale)


def paged_latent_gather_attention(q_lat, latent_pool, block_table, cur, *, value_width: int, scale: float):
    """The plain XLA paged latent decode step: gather each row's pages into
    a contiguous ``[B, L, W]`` copy and attend to it. What the Pallas
    kernel is checked against, and what runs where it cannot."""
    b = q_lat.shape[0]
    _, width, bs_ = latent_pool.shape
    mb = block_table.shape[1]
    rows = latent_pool[block_table].transpose(0, 1, 3, 2).reshape(b, mb * bs_, width)
    live = jnp.arange(mb * bs_)[None, :] <= cur[:, None]  # [B, L]
    scores = jnp.einsum("bqhw,bkw->bhqk", q_lat, rows).astype(jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(live[:, None, None, :], scores, -jnp.inf), axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhqk,bkc->bqhc", probs, rows[..., :value_width])


def _kernel_runner(fn, heads: int, kv_heads: int, pool_specs=None):
    """How to invoke a paged kernel ``fn(q, *pools, table, cur)`` under the
    active mesh (``pool_specs``: one spec a pool; default the K and V
    pools, heads over ``tensor``; a latent pool is replicated). A
    ``pallas_call`` is an opaque custom call XLA's partitioner cannot
    split, so a tensor-parallel pool must be fed per-shard via
    ``shard_map`` over the ``tensor`` axis (heads are independent in
    attention; the table/frontier are replicated) — the same treatment
    as ``sharded_pallas_attention``. Returns ``fn`` directly when no
    non-trivial tensor axis is active (or we're already inside a
    shard_map region), and None when heads don't divide the axis — the
    caller then uses the XLA gather path, which partitions naturally."""
    from ..utils.compat import in_manual_region

    if in_manual_region():
        return fn
    from .attention import active_mesh

    mesh = active_mesh()
    if mesh is None:
        return fn
    from ..parallel.mesh import axis_size

    n_t = axis_size(mesh, "tensor")
    if n_t <= 1:
        return fn
    if heads % n_t or (kv_heads % n_t and pool_specs is None):
        return None
    qspec = P(None, "tensor", None)
    if pool_specs is None:
        pool_specs = (P(None, None, "tensor", None),) * 2
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(qspec, *pool_specs, P(None, None), P(None)),
        out_specs=qspec,
        check_vma=False,
    )


def _path_names(path):
    return tuple(p.key if hasattr(p, "key") else str(p) for p in path)


def _scatter_pools(paged_cache, row_cache, write_row, table_updates, slot=None, summary_row=None, window_row=None,
                   new_index=None):
    """Blockify a dense per-row cache and scatter it into the pools at
    ``write_row``'s block ids; apply ``table_updates(name, leaf)`` to the
    ``block_table``/``summary_table``/``window_table``/``index`` leaves (or leave them untouched if it
    returns None); with a ``slot``, write the row cache's state leaves
    (:data:`STATE_LEAVES`) over that slot's; with a ``summary_row``, blockify
    the row cache's chunk summaries (:data:`SUMMARY_ROWS`) too and scatter them
    into the same pools at its block ids; with a ``window_row`` (a slot's ring,
    ``[ring]`` block ids of the windowed layers' pools), a pool that stands beside
    a ``window_table`` takes the LAST ``ring`` pages of the ``new_index`` rows
    alone, page ``p`` at ``window_row[p % ring]``: all that the band can still read."""
    dense = {_path_names(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(row_cache)[0]}
    ringed = {_path_names(p)[:-1] for p, _ in jax.tree_util.tree_flatten_with_path(paged_cache)[0]
              if _path_names(p)[-1] == "window_table"}

    def rows_of(prefix, name):
        """The dense leaf a pool holds the rows of: the one ``key`` /
        ``value`` / ``latent`` at or below the pool's module (a layer's own
        pool sits beside it; a scan's stack sits on the module that owns the scan)."""
        below = [leaf for p, leaf in dense.items() if p[: len(prefix)] == prefix and p[-1] == name]
        if len(below) != 1:
            raise ValueError(f"{len(below)} dense '{name}' leaves under {'/'.join(prefix) or '<root>'}, expected 1")
        return below[0]

    def write(path, leaf):
        names = _path_names(path)
        name, prefix = names[-1], names[:-1]
        if name in POOL_ROWS:
            row = rows_of(prefix, POOL_ROWS[name])
            latent = name == "latent_pool"  # pages [W, bs]; K/V pages [bs, H_kv, D]
            tail = 1 if latent else 2  # axes of one token's row
            lead = leaf.ndim - 2 - tail  # leading layer-scan axes (0 or 1)
            bs_ = leaf.shape[-1] if latent else leaf.shape[lead + 1]
            mb = write_row.shape[0]
            max_len = row.shape[lead + 1]
            pad = mb * bs_ - max_len
            if pad:
                widths = [(0, 0)] * (lead + 1) + [(0, pad)] + [(0, 0)] * tail
                row = jnp.pad(row, widths)
            # absorb the B=1 row axis while blockifying
            # a token's row as the pool lays it out: the dense leaf's [H_kv, D], or lane-folded (pool_lane_fold)
            token = row.shape[-1:] if latent else leaf.shape[-2:]
            blocks = row.reshape(*leaf.shape[:lead], mb, bs_, *token)
            if latent:
                blocks = blocks.swapaxes(-1, -2)
            ids = write_row
            if prefix in ringed:
                # a windowed layer's pool: the pages the ring can hold, ``last - ring + 1 .. last`` (``last`` the page of
                # the prompt's last row), cut out of the row cache in one slice and written each at its ring entry;
                # a page the prompt does not reach (a short prompt's) goes to the trash sink
                entries = window_row.shape[0]
                last = jnp.maximum(new_index - 1, 0) // bs_
                first = jnp.clip(last - entries + 1, 0, mb - entries)
                pages = first + jnp.arange(entries)
                blocks = jax.lax.dynamic_slice_in_dim(blocks, first, entries, axis=lead)
                ids = jnp.where(pages <= last, window_row[pages % entries], 0)
            def scatter(leaf, ids, blocks):
                """``blocks`` ``[.., n, bs, *token]`` (or latent pages) written at the pool's block ``ids`` ``[n]``."""
                sel = (slice(None),) * lead + (ids,)
                if not latent and 1 < leaf.shape[-2] < SUBLANES:
                    # A head axis of 2..7 is under a sublane tile: around a scatter of whole ``[bs, H, W]`` blocks the
                    # TPU compiler re-lays the pool with ``bs`` innermost and back, two copies of the whole pool a
                    # paste (read in the compile for a described v5e; 15 ms a paste at 2.4 GB of pools on the chip).
                    # Through the flat view ``[NB, bs * H, W]``, which is a bitcast, it scatters in place.
                    flat = (*leaf.shape[: lead + 1], bs_ * leaf.shape[-2], leaf.shape[-1])
                    blocks = blocks.reshape(*blocks.shape[: lead + 1], *flat[-2:])
                    return leaf.reshape(flat).at[sel].set(blocks.astype(leaf.dtype)).reshape(leaf.shape)
                return leaf.at[sel].set(blocks.astype(leaf.dtype))

            leaf = scatter(leaf, ids, blocks)
            if summary_row is not None:
                # the summaries of the prompt's chunks, rows of the pool's own shape: pages of the same pool
                pooled = rows_of(prefix, SUMMARY_ROWS[name])
                pages = summary_row.shape[0]
                pooled = jnp.pad(pooled, [(0, 0)] * (lead + 1) + [(0, pages * bs_ - pooled.shape[lead + 1])] + [(0, 0)] * tail)
                leaf = scatter(leaf, summary_row, pooled.reshape(*leaf.shape[:lead], pages, bs_, *token))
            return leaf
        if name in TABLES or name == "index":
            out = table_updates(name, leaf)
            return leaf if out is None else out
        if name in STATE_LEAVES:
            if slot is None:
                return leaf
            return jax.lax.dynamic_update_index_in_dim(leaf, dense[names][0].astype(leaf.dtype), slot, 0)
        raise ValueError(f"unexpected paged cache leaf {'/'.join(names)}")

    return jax.tree_util.tree_map_with_path(write, paged_cache)


def paste_row(paged_cache, row_cache, write_row, table_row, slot, new_index, summary_row=None, window_row=None):
    """Install a dense prefill row cache into the pool for ``slot``.

    ``row_cache`` is the ordinary dense per-row cache a prefill program
    produced (leaves ``key``/``value`` ``[..., 1, max_len, H_kv, D]``);
    every leaf is blockified and scattered at ``write_row``'s pool ids,
    and ``slot``'s table row / frontier index are set to ``table_row`` /
    ``new_index``. ``write_row`` and ``table_row`` differ exactly on
    entries the admit must NOT write: pad entries and shared prefix
    blocks point at the trash sink in ``write_row`` (shared content is
    written once, at registration — rewriting it per admit would race
    other slots decoding against it and waste the write traffic), while
    ``table_row`` keeps the real ids for reads. A state-space layer's
    state (:data:`STATE_LEAVES`) replaces ``slot``'s whole: whatever an
    idle slot stepped into it in the meantime is never read. With a
    ``summary_row`` (a cache with a ``summary_table``: EVA) the row cache's
    chunk summaries go to that row's pages of the same pools, and the row is
    ``slot``'s summary table: an entry the request will never read through
    is the trash sink, in the table and for the write alike. With a
    ``window_row`` (a cache with ``window_table`` leaves: a pool and a table a
    kind of layer) the windowed layers take the last ``ring`` pages of the row
    cache at that row's blocks, and the row is ``slot``'s ring; ``write_row``
    and ``table_row`` are the other layers'. Pure — jit once.
    """
    rows = {"block_table": table_row, "summary_table": summary_row, "window_table": window_row}

    def tables(name, leaf):
        if name in rows:
            sel = (slice(None),) * (leaf.ndim - 2) + (slot,)
            return leaf.at[sel].set(rows[name].astype(leaf.dtype))
        sel = (slice(None),) * (leaf.ndim - 1) + (slot,)
        return leaf.at[sel].set(jnp.asarray(new_index, leaf.dtype))

    return _scatter_pools(paged_cache, row_cache, write_row, tables, slot=slot, summary_row=summary_row,
                          window_row=window_row, new_index=new_index)


def paste_blocks(paged_cache, row_cache, write_row):
    """Write pool content only (no slot table/index): used once per
    registered prefix to install its full blocks as the canonical shared
    content every aliasing request reads; recurrent state is no block's
    and stays as it is. Pure — jit once."""
    return _scatter_pools(paged_cache, row_cache, write_row, lambda name, leaf: None)


def set_table_row(paged_cache, slot, table_row):
    """Replace ``slot``'s block-table row (leaving pools and frontier
    untouched): the engine's window-recycling path re-points expired
    entries at the trash sink as the frontier moves past them. Pure —
    jit once."""

    def write(path, leaf):
        if _path_names(path)[-1] == "block_table":
            sel = (slice(None),) * (leaf.ndim - 2) + (slot,)
            return leaf.at[sel].set(table_row.astype(leaf.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(write, paged_cache)


# a slot's tables: the whole context's, an EVA layer's summary pages, a windowed layer's ring
TABLES = ("block_table", "summary_table", "window_table")
# the leaves a retirement writes (:func:`clear_slot`): a slot's table rows, its frontier, its recurrent state
CLEARED_LEAVES = (*TABLES, "index", *STATE_LEAVES)


def _cleared(name: str, leaf, slot):
    """A leaf named in :data:`CLEARED_LEAVES` as a retirement of ``slot`` leaves it."""
    if name in TABLES:
        sel = (slice(None),) * (leaf.ndim - 2) + (slot,)
        return leaf.at[sel].set(jnp.zeros((leaf.shape[-1],), leaf.dtype))
    if name == "index":
        sel = (slice(None),) * (leaf.ndim - 1) + (slot,)
        return leaf.at[sel].set(jnp.zeros((), leaf.dtype))
    return jax.lax.dynamic_update_index_in_dim(leaf, jnp.zeros(leaf.shape[1:], leaf.dtype), slot, 0)  # STATE_LEAVES


def clear_slot(paged_cache, slot):
    """Re-point ``slot``'s table row at the trash sink and zero its
    frontier. MUST reach the device once a slot has retired, before the
    next paste into it and before the next decode tick: the static tick
    keeps computing (and writing) for every slot, and a stale table would
    corrupt blocks after they are freed and reallocated. A state-space
    layer's state is zeroed too: the tick goes on stepping the free slot
    (token 0 at position 0 from there: finite, and never read) until the
    next :func:`paste_row` replaces it whole. Pure — jit it."""

    def write(path, leaf):
        name = _path_names(path)[-1]
        return _cleared(name, leaf, slot) if name in CLEARED_LEAVES else leaf

    return jax.tree_util.tree_map_with_path(write, paged_cache)


def clear_slots(paged_cache, slots, n):
    """:func:`clear_slot` for ``slots[:n]`` in ONE program: what the engine
    runs for a tick's retirements (``slots`` is ``[num_slots]`` int32,
    whatever stands past ``n`` is not read). A loop of ``n`` trips over the
    leaves a retirement touches (tables, frontiers, recurrent state), each
    written in place one slot at a time, so the program moves a retired
    slot's bytes and no other's; the pools pass it by. Pure — jit it."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(paged_cache)
    names = [_path_names(path)[-1] for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    touched = [i for i, name in enumerate(names) if name in CLEARED_LEAVES]

    def clear_one(k, written):
        return [_cleared(names[i], leaf, slots[k]) for i, leaf in zip(touched, written)]

    for i, leaf in zip(touched, jax.lax.fori_loop(0, n, clear_one, [leaves[i] for i in touched])):
        leaves[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, leaves)


class BlockAllocator:
    """Host-side free list over pool blocks ``1..num_blocks-1`` (block 0
    is the trash sink and is never handed out)."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(f"pool needs >= 2 blocks (one is the trash sink), got {num_blocks}")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int):
        """``n`` block ids, or None if the pool can't satisfy the request
        (callers keep the request queued and retry after a retirement)."""
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def free(self, ids) -> None:
        for i in ids:
            if not 0 < i < self.num_blocks:
                raise ValueError(f"bad block id {i}")
            self._free.append(i)
