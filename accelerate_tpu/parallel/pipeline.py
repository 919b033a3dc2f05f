"""Pipeline parallelism: GPipe microbatch schedule over the ``pipe`` mesh axis.

Reference parity target: ``prepare_pippy`` (reference: src/accelerate/
inference.py:124-184) — torch.distributed.pipelining ``ScheduleGPipe`` with
auto split points, rank 0 feeding microbatches and the last rank collecting
(reference: inference.py:82-121). The TPU-native design is different in kind:

* stages are a **mesh axis**, not processes. Per-layer parameters are stacked
  on a leading layer dim (the ``lax.scan``-over-layers layout our models
  already use) and sharded over ``pipe``; each device applies its contiguous
  chunk of layers with an inner ``lax.scan``.
* the schedule is a single ``lax.scan`` over ``M + S - 1`` ticks inside
  ``shard_map``: every tick each device runs its stage, then hands its
  activation to the next stage via ``lax.ppermute`` (neighbour ICI traffic
  only — the TPU analogue of pippy's P2P sends).
* the whole schedule is differentiable (AD through ``ppermute``/``scan``), so
  unlike the reference — whose pipeline is inference-only — training works.

The GPipe bubble is the usual (S-1)/(M+S-1); raise ``num_microbatches`` to
amortise. Activation shape must be stage-invariant (classic GPipe), so
embedding / head layers run outside the pipelined trunk — see
:func:`prepare_pipeline`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import BATCH_AXES, axis_size, axis_spec


def stage_sharding(mesh: Mesh, axis_name: str = "pipe") -> NamedSharding:
    """Sharding for stacked per-layer params: leading (layer) dim over the
    pipe axis, i.e. stage *i* physically holds only its own layers."""
    return NamedSharding(mesh, P(axis_name))


def _gpipe_local(
    layer_params,
    x,
    broadcast_args,
    layer_fn: Callable,
    axis_name: str,
    n_stages: int,
    num_microbatches: int,
    batched_arg_mask: tuple,
    remat: bool,
    interleave: int = 1,
    scatter_output: bool = False,
):
    """Per-device GPipe body (runs under shard_map).

    layer_params: pytree, leaves [L_local, ...] — this stage's layers.
    x: [B_local, ...] this data-shard's batch (replicated over ``pipe``).
    broadcast_args: extras for layer_fn; entries flagged in
    ``batched_arg_mask`` share x's batch dim and are microbatched alongside
    it (stage i works on microbatch t-i at tick t, so they are indexed by
    that offset); the rest pass through whole.

    ``interleave > 1`` splits each microbatch into that many independent
    row blocks per tick: block j's ppermute issues while block j+1
    computes, so all but the last permute per tick hides behind compute
    (the in-flight handoff cannot be carried across scan iterations in
    JAX, so overlap has to come from within the tick).
    """
    m = num_microbatches
    b_mb = x.shape[0] // m
    k = interleave if interleave > 1 and b_mb % interleave == 0 else 1
    idx = lax.axis_index(axis_name)
    mb = x.reshape(m, k, b_mb // k, *x.shape[1:])
    args_mb = tuple(
        a.reshape(m, k, b_mb // k, *a.shape[1:]) if batched else a
        for a, batched in zip(broadcast_args, batched_arg_mask)
    )

    def apply_stage(h, mb_idx, j):
        args = tuple(
            a[mb_idx, j] if batched else a for a, batched in zip(args_mb, batched_arg_mask)
        )

        def body(carry, p):
            return layer_fn(p, carry, *args), None

        out, _ = lax.scan(body, h, layer_params)
        return out

    if remat:
        apply_stage = jax.checkpoint(apply_stage, static_argnums=(2,))

    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def tick(carry, t):
        state, out = carry  # state [k, b_mb/k, ...]
        # stage i works on microbatch t-i; clamp covers fill/drain ticks
        # whose results are never written
        mb_idx = jnp.clip(t - idx, 0, m - 1)
        feed_idx = jnp.minimum(t, m - 1)
        ys, sends = [], []
        for j in range(k):  # static unroll: permute j overlaps compute j+1
            h = jnp.where(idx == 0, mb[feed_idx, j], state[j])
            y = apply_stage(h, mb_idx, j)
            ys.append(y)
            sends.append(lax.ppermute(y, axis_name, perm))
        y_full = jnp.stack(ys)  # [k, b_mb/k, ...]
        state = jnp.stack(sends)
        # the last stage finishes microbatch t-(S-1) at tick t
        w = t - (n_stages - 1)
        slot = jnp.clip(w, 0, m - 1)
        write = (idx == n_stages - 1) & (w >= 0)
        out = lax.dynamic_update_index_in_dim(
            out,
            jnp.where(write, y_full, lax.dynamic_index_in_dim(out, slot, keepdims=False)),
            slot,
            0,
        )
        return (state, out), None

    state0 = jnp.zeros_like(mb[0])
    out0 = jnp.zeros_like(mb)
    (_, out), _ = lax.scan(tick, (state0, out0), jnp.arange(m + n_stages - 1))
    # the result lives on the last stage only
    masked = jnp.where(idx == n_stages - 1, out, jnp.zeros_like(out))
    if scatter_output:
        # reduce-scatter over the microbatch dim: each stage keeps its
        # contiguous m-block — HALF the wire traffic of the old full-buffer
        # psum (ring reduce-scatter moves (S-1)/S vs all-reduce's
        # 2(S-1)/S), no replicated [M,...] buffer, and downstream consumers
        # see a pipe-sharded batch layout (better, not just equal: the loss
        # then reduces over pipe shards too instead of recomputing on
        # identical replicas)
        out = lax.psum_scatter(masked, axis_name, scatter_dimension=0, tiled=True)
        return out.reshape(out.shape[0] * out.shape[1] * out.shape[2], *out.shape[3:])
    # fallback (microbatches don't divide over stages): replicate via psum
    out = lax.psum(masked, axis_name)
    return out.reshape(x.shape[0], *out.shape[3:])


def pipeline_apply(
    layer_fn: Callable,
    layer_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "pipe",
    batch_axes: Sequence[str] = BATCH_AXES,
    broadcast_args: tuple = (),
    batched_args: Optional[Sequence[bool]] = None,
    remat: bool = False,
    param_specs=None,
    interleave: int = 1,
) -> jax.Array:
    """Run ``x`` through a stack of layers pipelined over ``axis_name``.

    ``layer_params`` leaves are stacked ``[L, ...]`` (the scan-over-layers
    layout) and should be placed with :func:`stage_sharding`; ``L`` must
    divide by the pipe-axis size. ``layer_fn(p, h, *broadcast_args) -> h``
    applies one layer and must preserve ``h``'s shape. ``broadcast_args``
    are extras visible to every stage; by default args whose leading dim
    equals the batch (e.g. position ids [B, S]) are sharded and
    microbatched with ``x`` and anything else is replicated whole — pass
    ``batched_args`` (one bool per extra) to pin it explicitly when the
    shape heuristic would guess wrong (e.g. a replicated [B, k] table).

    ``param_specs`` (optional pytree of PartitionSpecs, leading entry
    ``pipe``) composes the stage split with other axes — e.g.
    ``P("pipe", None, "tensor")`` for Megatron column splits inside each
    stage; ``layer_fn`` then sees per-device shards and must psum over
    ``tensor`` itself (it runs under shard_map).

    ``interleave=2`` splits each microbatch into two row blocks per tick so
    each block's stage-handoff ppermute overlaps the other block's compute
    (hides ICI latency when per-block compute >= permute time; ignored when
    the per-device microbatch rows don't divide).
    """
    n_stages = mesh.shape[axis_name]
    if n_stages == 1:
        def body(carry, p):
            return layer_fn(p, carry, *broadcast_args), None

        out, _ = lax.scan(body, x, layer_params)
        return out

    n_layers = jax.tree.leaves(layer_params)[0].shape[0]
    if n_layers % n_stages != 0:
        raise ValueError(f"{n_layers} layers do not divide over {axis_name}={n_stages} stages")
    bspec = axis_spec(mesh, batch_axes)
    d_shards = axis_size(mesh, batch_axes)
    if (x.shape[0] // d_shards) % num_microbatches != 0:
        raise ValueError(
            f"per-shard batch {x.shape[0]}/{d_shards} must divide into {num_microbatches} microbatches"
        )

    if param_specs is None:
        param_specs = jax.tree.map(lambda l: P(axis_name), layer_params)
    x_spec = P(bspec)
    # when microbatches divide over stages, the output comes back
    # reduce-scattered: batch dim sharded (data-major, then pipe) instead of
    # replicated across pipe — see _gpipe_local
    scatter_output = num_microbatches % n_stages == 0
    if scatter_output:
        batch_axes_t = () if bspec is None else (bspec if isinstance(bspec, tuple) else (bspec,))
        out_spec = P(batch_axes_t + (axis_name,))
    else:
        out_spec = x_spec
    # extras sharing x's batch dim are sharded/microbatched with it
    if batched_args is not None:
        if len(batched_args) != len(broadcast_args):
            raise ValueError(f"batched_args has {len(batched_args)} entries for {len(broadcast_args)} broadcast_args")
        batched_arg_mask = tuple(bool(b) for b in batched_args)
    else:
        batched_arg_mask = tuple(
            getattr(a, "ndim", 0) >= 1 and a.shape[0] == x.shape[0] for a in broadcast_args
        )
    arg_specs = tuple(x_spec if b else P() for b in batched_arg_mask)
    fn = jax.shard_map(
        functools.partial(
            _gpipe_local,
            layer_fn=layer_fn,
            axis_name=axis_name,
            n_stages=n_stages,
            num_microbatches=num_microbatches,
            batched_arg_mask=batched_arg_mask,
            remat=remat,
            interleave=interleave,
            scatter_output=scatter_output,
        ),
        mesh=mesh,
        in_specs=(param_specs, x_spec, arg_specs),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(layer_params, x, broadcast_args)


@dataclass(eq=False)  # identity hash so the object can key a jit cache
class PipelinedModel:
    """A model split as ``pre -> pipelined trunk -> post`` (the role of the
    reference's pippy-wrapped module, inference.py:145-163: its auto split
    becomes "stack the homogeneous trunk, shard over ``pipe``").

    ``pre_fn(pre_params, *inputs) -> (h, broadcast_args)`` produces the
    stage-invariant activation; ``post_fn(post_params, h) -> out`` consumes
    it. Calling the object runs the full forward.
    """

    pre_fn: Callable
    layer_fn: Callable
    post_fn: Callable
    params: Any  # {"pre": ..., "layers": ..., "post": ...}
    mesh: Mesh
    num_microbatches: int
    axis_name: str = "pipe"
    batch_axes: Sequence[str] = BATCH_AXES
    remat: bool = False

    def __call__(self, params, *inputs):
        h, bcast = self.pre_fn(params["pre"], *inputs)
        h = pipeline_apply(
            self.layer_fn,
            params["layers"],
            h,
            mesh=self.mesh,
            num_microbatches=self.num_microbatches,
            axis_name=self.axis_name,
            batch_axes=self.batch_axes,
            broadcast_args=bcast,
            remat=self.remat,
        )
        return self.post_fn(params["post"], h)

    def shard_params(self, params=None):
        """device_put the param tree: trunk over ``pipe``, pre/post replicated
        (shard further with the model's own rules if composing with TP)."""
        params = self.params if params is None else params
        rep = NamedSharding(self.mesh, P())
        stage = stage_sharding(self.mesh, self.axis_name)
        return {
            "pre": jax.device_put(params["pre"], rep),
            "layers": jax.tree.map(lambda l: jax.device_put(l, stage), params["layers"]),
            "post": jax.device_put(params["post"], rep),
        }


def prepare_pipeline(
    pre_fn: Callable,
    layer_fn: Callable,
    post_fn: Callable,
    params,
    *,
    mesh: Mesh,
    num_microbatches: int = 4,
    axis_name: str = "pipe",
    batch_axes: Sequence[str] = BATCH_AXES,
    remat: bool = False,
) -> PipelinedModel:
    """Build a :class:`PipelinedModel` with its trunk params sharded over the
    ``pipe`` axis (API analogue of ``prepare_pippy``, reference
    inference.py:124). Returns the model; call it like a jitted forward."""
    pm = PipelinedModel(
        pre_fn=pre_fn,
        layer_fn=layer_fn,
        post_fn=post_fn,
        params=params,
        mesh=mesh,
        num_microbatches=num_microbatches,
        axis_name=axis_name,
        batch_axes=batch_axes,
        remat=remat,
    )
    pm.params = pm.shard_params(params)
    return pm
