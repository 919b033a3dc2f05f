"""Attention ops: XLA reference implementation + Pallas flash-attention
dispatch.

The reference framework has no attention kernels at all (it delegates to
torch models); this module exists because the build is a *framework with a
model zoo* and attention is the hot op. Dispatch policy:

* non-TPU backend, or a mask, dropout or softcap the kernel has no branch
  for -> plain XLA einsum attention (XLA fuses the softmax chain well);
* on TPU the automatic choice is a pure function of the call's shapes and
  of whether it may be differentiated (:func:`prefers_flash`): Pallas flash
  attention (:mod:`accelerate_tpu.ops.pallas_attention`, O(S) memory, the
  scores in a VMEM tile) from ``FLASH_MIN_SEQ`` query positions for a call
  with a backward pass, from ``FLASH_MIN_SEQ_FORWARD`` for a forward-only
  one (a prefill that starts a cache says so: ``forward_only=True``); XLA's
  product below. Each constant says what it was measured on;
* ``seq``-sharded activations -> ring attention
  (:mod:`accelerate_tpu.parallel.ring_attention`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# A call that may be differentiated: below this many query positions the
# quadratic XLA path is faster than the Pallas kernels' grid overhead.
# Measured on v5e, forward AND backward (three kernels), batch 4 x 12
# heads x 64 dim (BERT-like), value-fetch sync: seq 1024 flash is 0.86x
# XLA, seq 2048 flash is 1.82x — the crossover sits between them. No cell
# measures a train step at 1024-2047 tokens, so it stands as it was.
FLASH_MIN_SEQ = 2048
# A forward-only call (a bucket's prefill: one sequence, no backward
# kernels, XLA materialises [heads, S, S] float32 where the kernel keeps a
# [512, 512] tile). Measured on v5e, eight dependent calls a program
# (PERF.md 6, PR 49), kernel over XLA in ms a call: at 1024 queries 0.34 /
# 0.53 (32 heads x 128 on 8 K/V heads, mistral and granite), 0.47 / 0.76
# (48 x 128), 0.60 / 1.28 (64 x 128 under a band of 512), 0.33 / 0.52 (32 x
# 64), 0.23 / 0.24 (20 x 128 on one K/V head), 0.39 / 0.59 (32 x 192); at
# 512, 256, 128 and 64 XLA wins at every one of them (the kernel 1.2-1.9x
# XLA: 0.15 / 0.12 at 512, 0.12 / 0.08 at 256, 0.09 / 0.06 at 64). Inside a
# bucket's program XLA's product is dearer than alone (3.25 ms a layer at
# 64 x 1024 x 1024 where the kernel is 0.50), so the crossover is no lower.
FLASH_MIN_SEQ_FORWARD = 1024


def prefers_flash(sq: int, sk: int, heads: int, head_dim: int, *, forward_only: bool = False) -> bool:
    """The automatic choice between the flash kernel and XLA's product, as a pure function of the call's
    shapes (on a TPU, with no mask, dropout or softcap in the way: :func:`dot_product_attention` asks those
    first). The chip read one crossover for every head count and head size the cells run, so ``sq`` alone
    decides today; the other shapes are the rule's to read when a cell says they matter."""
    return sq >= (FLASH_MIN_SEQ_FORWARD if forward_only else FLASH_MIN_SEQ)


def dot_product_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, H_kv, D]
    v: jax.Array,  # [B, Sk, H_kv, D]
    mask: Optional[jax.Array] = None,  # bool, broadcastable to [B, H, Sq, Sk]
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    dropout_rate: float = 0.0,
    dropout_rng=None,
    mesh=None,  # pin the mesh for the sharded pallas path (else read from state at trace time)
    window: Optional[int] = None,  # Mistral band: keys <= q_pos - window are masked
    logit_softcap: Optional[float] = None,  # Gemma2: tanh-bound scores (XLA path only)
    forward_only: bool = False,  # the caller never differentiates this call: the choice reads the forward pass alone
) -> jax.Array:
    """Multi-head attention with optional GQA (H_kv divides H) and
    flash-kernel dispatch. Causal masking is bottom-right aligned when
    Sq != Sk (decode/chunked attention: query i attends keys
    ``0..Sk-Sq+i``). ``window`` adds the sliding-window band (requires
    ``causal``); on TPU at flash lengths it runs the banded kernel —
    O(S*W) — else the band folds into the XLA mask. Returns
    [B, Sq, H, D]."""
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim**-0.5
    seq_len = q.shape[1]
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window is a causal band)")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1 (got {window}); a 0-width band masks everything")

    explicit_flash = use_flash is not None
    if use_flash is None:
        use_flash = (
            jax.default_backend() == "tpu"
            and prefers_flash(seq_len, k.shape[1], q.shape[2], head_dim, forward_only=forward_only)
            and mask is None  # kernel supports causal/banded masking only
            and dropout_rate == 0.0
            and logit_softcap is None  # the kernel has no tanh-cap branch
        )
    if use_flash and logit_softcap is not None:
        raise ValueError("logit_softcap runs on the XLA path only; drop use_flash=True")
    if explicit_flash and use_flash and window is not None and jax.default_backend() != "tpu":
        # the scan fallback has no band support: refuse the explicit
        # request (consistent with the mask/dropout guards below). The
        # auto path never picks flash off-TPU, so it needs no fallback.
        raise ValueError("banded flash (window=) runs on the TPU kernel only; drop use_flash=True off-TPU")
    if use_flash:
        if mask is not None:
            raise ValueError(
                "flash attention supports causal (optionally banded via window=) masking only; "
                "pass mask=None or use_flash=False"
            )
        if dropout_rate > 0.0 and dropout_rng is not None:
            raise ValueError("flash attention does not support attention-prob dropout; use_flash=False")
        if jax.default_backend() == "tpu":
            return sharded_pallas_attention(q, k, v, causal=causal, scale=scale, mesh=mesh, window=window)
        from .flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, scale=scale)

    if window is not None:
        s = seq_len
        q_pos = jnp.arange(s)[:, None] + (k.shape[1] - s)
        band = (jnp.arange(k.shape[1])[None, :] > q_pos - window)[None, None]
        mask = band if mask is None else (mask & band)
    return _xla_attention(
        q, k, v, mask, causal, scale, dropout_rate, dropout_rng, _softmax_dtype(),
        logit_softcap=logit_softcap,
    )


def softcap(x: jax.Array, cap) -> jax.Array:
    """Gemma2 logit softcapping: ``tanh(x / cap) * cap`` in ``x``'s dtype —
    the ONE definition shared by the XLA attention path, the KV-cache
    decode path, and the final-logits head."""
    c = jnp.asarray(cap, x.dtype)
    return jnp.tanh(x / c) * c


def _softmax_dtype():
    """The policy's attention-softmax dtype (trace-time read; None = f32).
    Opt-in bandwidth lever: the f32 [B, H, S, S] logits materialisation is
    the HBM-bound training step's biggest avoidable traffic
    (MixedPrecisionPolicy.softmax_dtype)."""
    from ..state import AcceleratorState

    state = AcceleratorState._shared_state
    policy = state.get("dtype_policy") if state.get("_initialized") else None
    return getattr(policy, "softmax_dtype", None)


def active_mesh():
    """The mesh model code should trace against: a ``mesh_context``
    override (generation.py pins the params' mesh there) wins over the
    Accelerator singleton's mesh; None when neither is set."""
    from ..parallel.sharding import context_mesh

    mesh = context_mesh()
    if mesh is not None:
        return mesh
    from ..state import AcceleratorState

    state = AcceleratorState._shared_state
    return state.get("mesh") if state.get("_initialized") else None


def sharded_pallas_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    mesh=None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Pallas flash attention that stays partitioned under GSPMD.

    ``pallas_call`` lowers to an opaque custom call, so jitting it directly
    on sharded activations makes XLA all-gather q/k/v and replicate the
    output (mesh-size multiple of memory + FLOPs). Attention is independent
    per batch element and per head, so we wrap the kernel in ``shard_map``
    over the batch (``data``/``fsdp``) and head (``tensor``) axes of the
    active mesh — each device runs the kernel on exactly its local block and
    no collective is emitted. Falls back to the bare kernel when no
    non-trivial mesh is active or shapes don't divide."""
    import functools

    from .pallas_attention import pallas_flash_attention

    kernel = functools.partial(
        pallas_flash_attention, causal=causal, scale=scale, interpret=interpret, window=window
    )
    # Already inside a shard_map region (e.g. the GPipe trunk): inputs are
    # per-shard blocks and axes are Manual — nesting another shard_map over
    # the same mesh is an error; the bare kernel is exactly right here.
    from ..utils.compat import in_manual_region

    if in_manual_region():
        return kernel(q, k, v)
    if mesh is None:
        # NOTE: resolved at trace time — a forward traced before the
        # Accelerator initialises bakes in the unsharded path (pass ``mesh``
        # explicitly to pin it; model code in models/ does).
        mesh = active_mesh()
    if mesh is None:
        return kernel(q, k, v)

    from ..parallel.mesh import BATCH_AXES, axis_size, axis_spec

    bspec = axis_spec(mesh, BATCH_AXES)
    hspec = axis_spec(mesh, "tensor")
    n_b, n_h = axis_size(mesh, BATCH_AXES), axis_size(mesh, "tensor")
    divisible = (
        q.shape[0] % n_b == 0
        and q.shape[2] % n_h == 0
        and k.shape[2] % n_h == 0  # GQA: kv heads must split the same way
    )
    if (bspec is None and hspec is None) or not divisible:
        return kernel(q, k, v)
    from jax.sharding import PartitionSpec as P

    spec = P(bspec, None, hspec, None)
    fn = jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)


def _xla_attention(
    q, k, v, mask, causal, scale, dropout_rate, dropout_rng, softmax_dtype=None, logit_softcap=None
):
    seq_len = q.shape[1]
    num_heads, num_kv = q.shape[-2], k.shape[-2]
    if num_kv != num_heads:  # GQA: repeat kv groups
        reps = num_heads // num_kv
        k = jnp.repeat(k, reps, axis=-2)
        v = jnp.repeat(v, reps, axis=-2)

    # [B,S,H,D] -> [B,H,Sq,Sk]. precision="highest": JAX's DEFAULT matmul
    # precision decomposes fp32 operands to bf16 passes (on TPU MXU and on
    # the oneDNN CPU backend), injecting ~1e-3 relative error into the
    # logits — enough to break fp32 parity with reference implementations.
    # bf16 operands are a single MXU pass either way, so the bf16 training
    # path is not slowed.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    # f32 softmax math by default; an explicit policy softmax_dtype (e.g.
    # bfloat16) skips the f32 [B, H, Sq, Sk] materialisation — the
    # HBM-bound step's biggest avoidable traffic (1.10x measured on the
    # BERT v5e step; MixedPrecisionPolicy.softmax_dtype)
    sm_dtype = jnp.dtype(softmax_dtype) if softmax_dtype is not None else jnp.float32
    logits = logits.astype(sm_dtype)
    if logit_softcap is not None:
        # Gemma2 attention softcapping: tanh-bound the scores BEFORE the
        # mask (HF order), keeping gradients finite at long context
        logits = softcap(logits, logit_softcap)
    if causal:
        offset = k.shape[1] - seq_len  # bottom-right alignment
        q_pos = jnp.arange(seq_len)[:, None] + offset
        k_pos = jnp.arange(k.shape[1])[None, :]
        causal_mask = q_pos >= k_pos
        logits = jnp.where(causal_mask[None, None], logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(sm_dtype).min)
    weights = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, weights.shape)
        weights = jnp.where(keep, weights / (1.0 - dropout_rate), 0.0)
    weights = weights.astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v, precision="highest")
