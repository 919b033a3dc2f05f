"""Pallas flash-attention kernel vs the XLA reference implementation.

Runs the TPU kernel in Pallas interpreter mode on CPU (shapes kept small —
interpret mode executes block-by-block in Python). Checks forward and all
three input gradients for: non-causal, causal, GQA, unpadded-odd sequence
lengths, and the decode case Sq < Sk (bottom-right causal alignment)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops.attention import dot_product_attention
from jax import shard_map
from accelerate_tpu.ops.pallas_attention import pallas_flash_attention


def _make_qkv(rng, b, sq, sk, h, h_kv, d, dtype=jnp.float32):
    keys = jax.random.split(rng, 3)
    q = jax.random.normal(keys[0], (b, sq, h, d), dtype)
    k = jax.random.normal(keys[1], (b, sk, h_kv, d), dtype)
    v = jax.random.normal(keys[2], (b, sk, h_kv, d), dtype)
    return q, k, v


def _ref(q, k, v, causal):
    return dot_product_attention(q, k, v, causal=causal, use_flash=False)


def _kernel(q, k, v, causal):
    return pallas_flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)


CASES = [
    # b, sq, sk, h, h_kv, d, causal
    pytest.param(2, 128, 128, 2, 2, 32, False, id="mha-noncausal"),
    pytest.param(2, 128, 128, 2, 2, 32, True, id="mha-causal"),
    pytest.param(1, 128, 128, 4, 2, 32, True, id="gqa-causal"),
    pytest.param(1, 100, 100, 2, 1, 32, True, id="odd-seq-padded"),
    pytest.param(1, 64, 192, 2, 2, 32, True, id="decode-sq-lt-sk"),
]


@pytest.mark.parametrize("b,sq,sk,h,h_kv,d,causal", CASES)
def test_forward_matches_reference(b, sq, sk, h, h_kv, d, causal):
    q, k, v = _make_qkv(jax.random.PRNGKey(0), b, sq, sk, h, h_kv, d)
    out = _kernel(q, k, v, causal)
    expected = _ref(q, k, v, causal)
    assert out.shape == expected.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "b,sq,sk,h,h_kv,d,causal",
    [
        pytest.param(1, 128, 128, 2, 2, 32, True, id="mha-causal"),
        pytest.param(1, 128, 128, 4, 2, 32, True, id="gqa-causal"),
        pytest.param(1, 100, 100, 2, 2, 32, False, id="odd-seq-noncausal"),
    ],
)
def test_gradients_match_reference(b, sq, sk, h, h_kv, d, causal):
    q, k, v = _make_qkv(jax.random.PRNGKey(1), b, sq, sk, h, h_kv, d)

    def loss_kernel(q, k, v):
        return (_kernel(q, k, v, causal) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref(q, k, v, causal) ** 2).sum()

    grads = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    expected = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, e, name in zip(grads, expected, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(e), atol=2e-3, rtol=2e-3, err_msg=f"d{name} mismatch"
        )


def _ref_banded(q, k, v, window):
    """Banded reference: causal + Mistral band via the XLA mask path."""
    sq, sk = q.shape[1], k.shape[1]
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)
    band = (jnp.arange(sk)[None, :] > q_pos - window)[None, None]
    return dot_product_attention(q, k, v, mask=band, causal=True, use_flash=False)


@pytest.mark.parametrize(
    "b,s,h,h_kv,d,window",
    [
        pytest.param(2, 128, 2, 2, 32, 40, id="mha-band"),
        pytest.param(1, 128, 4, 2, 32, 64, id="gqa-band-blockmult"),
        pytest.param(1, 100, 2, 2, 32, 17, id="odd-seq-odd-band"),
        pytest.param(1, 128, 2, 2, 32, 500, id="band-wider-than-seq"),
        pytest.param(1, 128, 2, 2, 32, 1, id="self-only-band"),
    ],
)
def test_banded_forward_matches_reference(b, s, h, h_kv, d, window):
    q, k, v = _make_qkv(jax.random.PRNGKey(5), b, s, s, h, h_kv, d)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True, window=window)
    want = _ref_banded(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_banded_decode_alignment_sq_lt_sk():
    """Band + bottom-right alignment (chunked prefill / decode shapes):
    the `sk - sq` offset threads through the band mask, the block skip,
    and the XLA fold identically."""
    q, k, v = _make_qkv(jax.random.PRNGKey(8), 1, 32, 128, 2, 2, 32)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True, window=40)
    want = _ref_banded(q, k, v, 40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_banded_gradients_match_reference():
    b, s, h, h_kv, d, window = 1, 128, 4, 2, 32, 40
    q, k, v = _make_qkv(jax.random.PRNGKey(6), b, s, s, h, h_kv, d)

    def loss_kernel(q, k, v):
        out = pallas_flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, interpret=True, window=window
        )
        return (out**2).sum()

    def loss_ref(q, k, v):
        return (_ref_banded(q, k, v, window) ** 2).sum()

    grads = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    expected = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, e, name in zip(grads, expected, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(e), atol=2e-3, rtol=2e-3, err_msg=f"d{name} mismatch"
        )


def test_banded_requires_causal():
    q, k, v = _make_qkv(jax.random.PRNGKey(7), 1, 64, 64, 2, 2, 32)
    with pytest.raises(ValueError, match="causal"):
        pallas_flash_attention(q, k, v, causal=False, interpret=True, window=8)
    from accelerate_tpu.ops.attention import dot_product_attention as dpa

    with pytest.raises(ValueError, match="causal"):
        dpa(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match=">= 1"):
        dpa(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match=">= 1"):
        pallas_flash_attention(q, k, v, causal=True, interpret=True, window=0)
    # explicit flash + band off-TPU must refuse, not silently go quadratic
    with pytest.raises(ValueError, match="TPU"):
        dpa(q, k, v, causal=True, window=8, use_flash=True)


def test_jit_and_scan_fallback_agree():
    """The jitted Pallas path and the lax.scan fallback agree bitwise-ish."""
    from accelerate_tpu.ops.flash_attention import flash_attention as scan_flash

    q, k, v = _make_qkv(jax.random.PRNGKey(2), 1, 128, 128, 2, 2, 32)
    fn = jax.jit(functools.partial(_kernel, causal=True))
    out = fn(q, k, v)
    out_scan = scan_flash(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_scan), atol=2e-5, rtol=2e-5)


def test_sharded_dispatch_stays_partitioned():
    """sharded_pallas_attention must run the kernel per-shard under
    shard_map: no all-gather in the HLO, output sharding preserved
    (regression: bare pallas_call is opaque to GSPMD and forced a
    mesh-wide all-gather + replicated output)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import MeshConfig
    from accelerate_tpu.ops.attention import sharded_pallas_attention

    mesh = MeshConfig(data=2, tensor=4).build()
    q, k, v = _make_qkv(jax.random.PRNGKey(3), 2, 128, 128, 8, 4, 32)
    shard = NamedSharding(mesh, P("data", None, "tensor", None))
    args = tuple(jax.device_put(x, shard) for x in (q, k, v))

    fn = jax.jit(
        functools.partial(sharded_pallas_attention, causal=True, mesh=mesh, interpret=True)
    )
    hlo = fn.lower(*args).compile().as_text()
    assert "all-gather" not in hlo, "sharded pallas dispatch must not all-gather q/k/v"
    out = fn(*args)
    assert out.sharding.spec == P("data", None, "tensor", None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)), atol=2e-3, rtol=2e-3)


def test_sharded_dispatch_falls_back_without_mesh():
    from accelerate_tpu.ops.attention import sharded_pallas_attention

    q, k, v = _make_qkv(jax.random.PRNGKey(4), 1, 128, 128, 2, 2, 32)
    out = sharded_pallas_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)), atol=2e-3, rtol=2e-3)


def test_sharded_dispatch_inside_shard_map():
    """Calling the sharded dispatch from within an existing shard_map region
    (e.g. the GPipe trunk) must use the bare kernel on the local block, not
    nest another shard_map (regression: nested shard_map over the same mesh
    raises a context-mesh mismatch at trace time)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import MeshConfig
    from accelerate_tpu.ops.attention import sharded_pallas_attention

    mesh = MeshConfig(data=8).build()
    q, k, v = _make_qkv(jax.random.PRNGKey(5), 8, 128, 128, 2, 2, 32)

    def local(q, k, v):
        return sharded_pallas_attention(q, k, v, causal=True, mesh=mesh, interpret=True)

    spec = P("data")
    fn = jax.jit(
        shard_map(local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    )
    shard = NamedSharding(mesh, spec)
    out = fn(*(jax.device_put(x, shard) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)), atol=2e-3, rtol=2e-3)
