"""Mixture-of-Experts: top-k routing + expert-parallel FFN.

The reference has **no** expert-parallel strategy — its only MoE support is
marking DeepSpeed MoE layer classes as ZeRO-3 leaves
(reference: src/accelerate/utils/dataclasses.py deepspeed_moe_layer_cls_names,
accelerator.py:2049). Expert parallelism is therefore a parity-plus
subsystem here, built the GSPMD way (GShard/Mesh-TF idiom):

* experts are **stacked params** with a leading expert dim, sharded over the
  ``expert`` mesh axis;
* token -> expert dispatch is a dense one-hot ``[tokens, experts, capacity]``
  mask consumed by einsums — XLA turns the sharded einsums into exactly the
  all-to-all shuffles a hand-written MPI MoE would do, and overlaps them;
* fixed per-expert ``capacity`` keeps every shape static (jit-friendly);
  overflow tokens fall through the residual connection (standard GShard
  behavior), and the load-balancing aux loss keeps overflow rare.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn


def top_k_routing(
    router_logits: jax.Array,  # [T, E]
    num_selected: int,
    capacity: int,
    norm_topk: bool = True,
):
    """GShard-style top-k token routing with fixed expert capacity.

    Returns ``(dispatch, combine, aux_loss)``:
    dispatch — bool [T, E, C], token t occupies slot c of expert e;
    combine — float [T, E, C], routing weight for the same slots
    (normalised over the selected experts when ``norm_topk``, the
    Mixtral convention; Qwen3-MoE checkpoints with
    ``norm_topk_prob=False`` keep the raw full-softmax probabilities —
    HF calls this "the only diff with the mixtral sparse moe block");
    aux_loss — load-balance loss (mean fraction routed x mean router prob,
    scaled by E; Shazeer/GShard form).
    """
    t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

    dispatch = jnp.zeros((t, e, capacity), jnp.bool_)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    remaining = probs
    # slots already taken per expert by earlier (higher-priority) choices
    fill = jnp.zeros((e,), jnp.int32)
    selected_mass = jnp.zeros((t,), jnp.float32)
    for _ in range(num_selected):  # num_selected is tiny and static
        choice = jnp.argmax(remaining, axis=-1)  # [T]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.int32)  # [T, E]
        # position of each token within its chosen expert's queue, offset by
        # slots filled in earlier rounds
        pos = (jnp.cumsum(onehot, axis=0) - 1) + fill[None, :]  # [T, E]
        pos_tok = jnp.sum(pos * onehot, axis=-1)  # [T]
        keep = pos_tok < capacity
        gate = jnp.sum(remaining * onehot, axis=-1)  # [T] prob of this choice
        slot = jax.nn.one_hot(jnp.clip(pos_tok, 0, capacity - 1), capacity, dtype=jnp.float32)
        contrib = (
            onehot.astype(jnp.float32)[:, :, None]
            * slot[:, None, :]
            * keep[:, None, None]
        )
        dispatch = dispatch | (contrib > 0)
        combine = combine + contrib * gate[:, None, None]
        selected_mass = selected_mass + gate * keep
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0)
        remaining = remaining * (1.0 - onehot)  # mask chosen expert out

    if norm_topk:
        # normalise combine weights over the actually-kept choices
        combine = combine / jnp.maximum(selected_mass, 1e-9)[:, None, None]

    # load-balance aux: E * mean_e(frac_tokens_e * mean_prob_e)
    frac = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux_loss


def moe_ffn(
    x: jax.Array,  # [T, d]
    router_kernel: jax.Array,  # [d, E]
    wi: jax.Array,  # [E, d, ff] (or gate/up pair for swiglu)
    wo: jax.Array,  # [E, ff, d]
    num_selected: int = 2,
    capacity_factor: float = 1.25,
    wi_gate: Optional[jax.Array] = None,  # [E, d, ff] for SwiGLU experts
    activation=nn.gelu,
    norm_topk: bool = True,
):
    """Dense-dispatch MoE feed-forward. Returns (out [T, d], aux_loss).

    All einsums are GSPMD-friendly: with ``wi/wo`` sharded over the
    ``expert`` axis and tokens over the batch axes, XLA inserts the
    dispatch/return all-to-alls automatically.
    """
    t, d = x.shape
    e = router_kernel.shape[-1]
    # GShard/Mixtral convention: capacity_factor scales the *per-assignment*
    # budget, so top-k routing gets k*T total slots before the factor
    capacity = max(1, int(capacity_factor * num_selected * t / e))
    logits = x @ router_kernel.astype(x.dtype)
    dispatch, combine, aux = top_k_routing(logits, num_selected, capacity, norm_topk=norm_topk)

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)  # all-to-all in
    if wi_gate is not None:
        h = nn.silu(jnp.einsum("ecd,edf->ecf", xe, wi_gate.astype(x.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", xe, wi.astype(x.dtype))
    else:
        h = activation(jnp.einsum("ecd,edf->ecf", xe, wi.astype(x.dtype)))
    ye = jnp.einsum("ecf,efd->ecd", h, wo.astype(x.dtype))  # [E, C, d]
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye)  # all-to-all out
    return out, aux


class MoEBlock(nn.Module):
    """Sparse SwiGLU FFN block (Mixtral-style): top-k routed experts with a
    shared residual path for dropped tokens. Expects [B, S, d]; returns
    [B, S, d]. The load-balancing aux loss is exposed via
    ``sow("intermediates", "moe_aux_loss")`` — read it from the mutable
    ``intermediates`` collection after ``apply``."""

    num_experts: int
    intermediate_size: int
    num_selected: int = 2
    capacity_factor: float = 1.25
    norm_topk: bool = True  # False = Qwen3-MoE's raw-softmax combine weights

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        ff, e = self.intermediate_size, self.num_experts
        router = self.param("router/kernel", nn.initializers.lecun_normal(), (d, e))
        wi_gate = self.param("experts/gate_proj", nn.initializers.lecun_normal(), (e, d, ff))
        wi_up = self.param("experts/up_proj", nn.initializers.lecun_normal(), (e, d, ff))
        wo = self.param("experts/down_proj", nn.initializers.lecun_normal(), (e, ff, d))
        flat = x.reshape(b * s, d)
        out, aux = moe_ffn(
            flat,
            router,
            wi_up,
            wo,
            num_selected=self.num_selected,
            capacity_factor=self.capacity_factor,
            wi_gate=wi_gate,
            norm_topk=self.norm_topk,
        )
        self.sow("intermediates", "moe_aux_loss", aux)
        return out.reshape(b, s, d)


def sigmoid_topk_routing(
    router_logits: jax.Array,  # [T, E]
    selection_bias: Optional[jax.Array],  # [E] or None
    num_selected: int,
    norm_topk: bool = True,
    scaling_factor: float = 1.0,
):
    """Dropless top-k routing as DeepSeek-V3-style checkpoints publish it
    (``topk_method="noaux_tc"`` with one group): scores are
    ``sigmoid(logits)`` in float32; the ``num_selected``
    experts of a token are the top-k of ``score + selection_bias`` — the
    bias *chooses* and does not weigh; the weights are the scores at
    those experts, divided by their sum when ``norm_topk``, times
    ``scaling_factor``. Returns ``(experts [T, k] int32, weights [T, k]
    float32)``. No capacity: every token keeps all its experts."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    choose = scores if selection_bias is None else scores + selection_bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(choose, num_selected)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scaling_factor


def softmax_topk_routing(router_logits: jax.Array, num_selected: int):
    """Dropless top-k routing as Granite's hybrid checkpoints publish it
    (``granitemoehybrid``'s ``TopKGating``): a token's ``num_selected``
    experts are its largest raw logits, and their weights a softmax over
    THOSE logits alone (not a softmax over every expert cut to its top),
    in float32. No bias, no scaling factor. Returns what
    :func:`sigmoid_topk_routing` does."""
    top, experts = jax.lax.top_k(router_logits.astype(jnp.float32), num_selected)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def _ragged_swiglu_ffn(xs, wi_gate, wi_up, wo, group_sizes):
    """The experts' three grouped products as ``jax.lax.ragged_dot`` calls: rows ``xs [m, d]`` sorted by group."""
    h = nn.silu(jax.lax.ragged_dot(xs, wi_gate.astype(xs.dtype), group_sizes))
    h = h * jax.lax.ragged_dot(xs, wi_up.astype(xs.dtype), group_sizes)
    return jax.lax.ragged_dot(h, wo.astype(xs.dtype), group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_swiglu_ffn(xs, wi_gate, wi_up, wo, group_sizes, interpret):
    from .pallas_grouped_matmul import grouped_swiglu_ffn

    return grouped_swiglu_ffn(xs, wi_gate, wi_up, wo, group_sizes, interpret=interpret)


def _kernel_swiglu_ffn_fwd(*args):
    return _kernel_swiglu_ffn(*args), args[:5]  # all but ``interpret``


def _kernel_swiglu_ffn_bwd(interpret, residuals, g):
    # the ragged_dot formulation's gradients: no cell trains this family, so the backward has no kernel
    *operands, group_sizes = residuals
    _, vjp = jax.vjp(lambda *ops: _ragged_swiglu_ffn(*ops, group_sizes), *operands)
    return (*vjp(g), None)


_kernel_swiglu_ffn.defvjp(_kernel_swiglu_ffn_fwd, _kernel_swiglu_ffn_bwd)


def dropless_moe_ffn(
    x: jax.Array,  # [T, d]
    experts: jax.Array,  # [T, k] int32: the experts each token goes to
    weights: jax.Array,  # [T, k]: their weights
    wi_gate: jax.Array,  # [E, d, ff]
    wi_up: jax.Array,  # [E, d, ff]
    wo: jax.Array,  # [E, ff, d]
    row_valid: Optional[jax.Array] = None,  # [T] bool: the tokens that count; None = every one
    first_expert: Optional[int] = None,  # the id, among the router's, of the first expert held; None = all are held
):
    """Routed SwiGLU experts with no capacity and no ``[T, E, C]`` mask:
    the ``T * k`` token-expert pairs are sorted by expert and the three
    products run as grouped matmuls, which read an expert's weights only if
    a pair reached it, so a decode step of 64 tokens and a prefill of 4096
    take the same path and no token is ever dropped, at any skew.

    **On a TPU** the products run in the Pallas kernels of
    :mod:`.pallas_grouped_matmul` (``gate`` and ``up`` in one call, ``down``
    in a second), whose row tile follows the pairs an expert gets
    (``row_tile(T * k, E)``: 16 rows at a decode tick, up to 128 in a
    prefill). **Off it**, and under a mesh of several devices, they are
    three ``jax.lax.ragged_dot`` calls, as is the backward everywhere.
    ``paged_kv.FORCE_KERNEL_INTERPRET`` runs the kernels interpreted
    (tests). Operands in ``x.dtype``, float32 accumulation, either way.

    ``row_valid`` names the tokens that count (a decode tick's slots in
    which a request decodes). The pairs of the others get the expert id
    ``E``: they sort behind every group, no group counts them, so no
    product visits them, no expert's weights are read for them alone, and
    their rows of ``out`` are exactly zero. The rows that count are what
    the call without the mask gives, bit for bit.

    ``first_expert`` says that the stacked matrices are a *share* of the
    experts the router chose among: the ``E`` experts ``first_expert ..
    first_expert + E - 1`` of its columns (one chip's share under expert
    parallelism). A pair whose expert lives elsewhere takes the same road
    as a token that does not count: behind the last group, visited by no
    product, zero in the sum. ``out`` is then the held experts' part of
    the routed sum, under the weights the router gave over all its
    experts; the shares of every chip add up to the whole. Nothing here
    stands in for the other chips or the exchange with them.

    Returns ``(out [T, d], group_sizes [E])``; ``group_sizes`` (pairs per
    held expert, summing to the pairs of the tokens that count which
    reached one) is what :func:`expert_load` reads."""
    from . import paged_kv
    from .attention import active_mesh

    t, k = experts.shape
    e = wi_gate.shape[0]
    flat = experts.reshape(t * k)
    counts = None if row_valid is None else jnp.repeat(row_valid, k)  # [T * k] bool: the pairs a product visits
    if first_expert is not None:
        flat = flat - first_expert
        held = (flat >= 0) & (flat < e)
        counts = held if counts is None else counts & held
    if counts is not None:
        flat = jnp.where(counts, flat, e)  # behind the last group, and in none
    order = jnp.argsort(flat, stable=True)  # pairs grouped by expert
    group_sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
    on_tpu = jax.default_backend() == "tpu"
    mesh = active_mesh()  # XLA's partitioner cannot split a pallas_call: over several devices, ragged_dot
    with jax.named_scope("moe.experts"):
        xs = x[order // k]  # [T*k, d]: pair i of the sorted list belongs to token order[i] // k
        if (on_tpu or paged_kv.FORCE_KERNEL_INTERPRET) and (mesh is None or mesh.size == 1):
            ys = _kernel_swiglu_ffn(xs, wi_gate, wi_up, wo, group_sizes, not on_tpu)  # [T*k, d]
        else:
            ys = _ragged_swiglu_ffn(xs, wi_gate, wi_up, wo, group_sizes)
        # back to token order by a gather (the inverse permutation), then the weighted sum
        back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k, dtype=order.dtype))
        ys = ys[back].reshape(t, k, -1)
        if counts is not None:
            # a row no product visited is whatever the kernel's output buffer held: zero before the sum (0 x NaN is NaN)
            ys = jnp.where(row_valid[:, None, None] if first_expert is None else counts.reshape(t, k, 1), ys, 0)
        out = jnp.einsum("tkd,tk->td", ys.astype(jnp.float32), weights.astype(jnp.float32)).astype(x.dtype)
    return out, group_sizes


EXPERT_LOAD = "expert_load"  # the flax collection a routed FFN sows its counts into
_LOAD_COUNTS: Optional[list] = None


def expert_load(group_sizes: jax.Array, pairs: int) -> jax.Array:
    """``[distinct experts with a pair, most pairs on one expert, (expert, row tile) visits of one
    product, pairs the products multiplied]`` (``[4]`` int32) of one routed FFN call over ``pairs = T * k``
    sorted rows. The third is the grid of the grouped kernel (:mod:`.pallas_grouped_matmul`) at the row
    tile that row count gives: over the first it says how many row tiles an expert's pairs lie in, 1.0
    where none straddles. The fourth is ``pairs`` less the pairs of the tokens ``row_valid`` left out."""
    from .pallas_grouped_matmul import row_tile, tile_visits

    visits = tile_visits(group_sizes, row_tile(pairs, group_sizes.shape[0]))
    return jnp.stack([jnp.sum(group_sizes > 0), group_sizes.max(), jnp.sum(visits), jnp.sum(group_sizes)])


@contextlib.contextmanager
def expert_load_counts():
    """Trace-time request, in the manner of ``paged_kv.paged_mode``: a
    model's ``apply_fn`` called inside it makes the ``expert_load``
    collection mutable and appends what its routed FFNs sowed there
    (:func:`expert_load` of each call) to the list this yields. The values
    belong to the caller's trace, so the caller returns them as outputs of
    its own program (the serving engine's decode tick does, beside the
    tokens). Outside the context nothing is sown and nothing computed."""
    global _LOAD_COUNTS
    prev, _LOAD_COUNTS = _LOAD_COUNTS, []
    try:
        yield _LOAD_COUNTS
    finally:
        _LOAD_COUNTS = prev


def requested_expert_load() -> Optional[list]:
    """The list of the innermost :func:`expert_load_counts`, or None."""
    return _LOAD_COUNTS
