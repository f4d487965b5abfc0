"""Pallas flash-attention kernel correctness (interpret mode on CPU).

Oracle: dense attention — same pattern as the ring/Ulysses tests. On CPU
the kernel runs in Pallas interpret mode; on TPU the identical code
compiles to a Mosaic kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu.ops import dense_attention, flash_attention

B, S, H, D = 2, 64, 2, 16


def make_qkv(seed: int = 0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [16, 32, 64])
def test_flash_matches_dense(causal: bool, block: int) -> None:
    q, k, v = make_qkv()
    ref = dense_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=block, block_k=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_uneven_blocks() -> None:
    q, k, v = make_qkv(seed=1)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_bf16() -> None:
    q, k, v = make_qkv(seed=2, dtype=jnp.bfloat16)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(16, 16), (16, 32), (32, 16)])
def test_flash_gradients_match_dense(causal: bool, blocks) -> None:
    """Backward runs through the Pallas dq / dkv kernels (not recompute)."""
    bq, bk = blocks
    q, k, v = make_qkv(seed=3)

    def loss_f(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk) ** 2
        )

    def loss_d(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


def test_flash_gradients_bf16() -> None:
    q, k, v = make_qkv(seed=6, dtype=jnp.bfloat16)

    def loss_f(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, block_q=16, block_k=16).astype(jnp.float32) ** 2
        )

    def loss_d(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gd, np.float32), atol=0.1
        )


def test_flash_indivisible_raises() -> None:
    q, k, v = make_qkv(seed=4)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=48, block_k=48)


def test_flash_default_blocks_snap_to_divisor() -> None:
    """Default blocks auto-pick the largest divisor of S (<= 512): a seq
    len like 160 (divisible by 32, not by 512) must run, not raise."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (1, 160, 2, 16)) for kk in ks)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_transformer_flash_matches_dense() -> None:
    from torchsnapshot_tpu.models import transformer as T

    base = dict(
        vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=S, dtype=jnp.float32,
    )
    params = T.init_params(jax.random.PRNGKey(0), T.TransformerConfig(**base))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, 128)
    ref = T.forward(params, tokens, T.TransformerConfig(**base))
    out = T.forward(
        params, tokens,
        T.TransformerConfig(**base, attn_impl="flash", attn_block_size=16),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_flash_sharded_matches_dense() -> None:
    """shard_mapped kernel over a ('data','model') mesh == dense oracle."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu.ops.pallas_attention import flash_attention_sharded

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    q, k, v = make_qkv(seed=7)
    ref = dense_attention(q, k, v, causal=True)
    qs, ks_, vs = (
        jax.device_put(t, NamedSharding(mesh, P("data", None, "model", None)))
        for t in (q, k, v)
    )
    out = jax.jit(
        lambda q, k, v: flash_attention_sharded(q, k, v, mesh, causal=True)
    )(qs, ks_, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_sharded_head_indivisible_raises() -> None:
    from jax.sharding import Mesh

    from torchsnapshot_tpu.ops.pallas_attention import flash_attention_sharded

    mesh = Mesh(np.array(jax.devices()[:3]).reshape(1, 3), ("data", "model"))
    q, k, v = make_qkv(seed=8)  # H=2, not divisible by 3
    with pytest.raises(ValueError, match="divisible"):
        flash_attention_sharded(q, k, v, mesh)


def test_transformer_flash_with_mesh_matches_dense() -> None:
    """attn_impl='flash' under a tp mesh routes through the shard_mapped
    kernel and matches the meshless dense forward."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu.models import transformer as T

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    base = dict(
        vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=S, dtype=jnp.float32,
    )
    params = T.init_params(jax.random.PRNGKey(0), T.TransformerConfig(**base))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, S), 0, 128)
    ref = T.forward(params, tokens, T.TransformerConfig(**base, attn_impl="dense"))
    st = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
    out = jax.jit(
        lambda p, t: T.forward(
            p, t,
            T.TransformerConfig(**base, attn_impl="flash", attn_block_size=16),
            mesh=mesh,
        )
    )(params, st)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_ulysses_flash_inner() -> None:
    from jax.sharding import Mesh

    from torchsnapshot_tpu.ops import ulysses_attention_sharded

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("seq",))
    q, k, v = make_qkv(seed=5)
    ref = dense_attention(q, k, v, causal=True)
    out = ulysses_attention_sharded(
        q, k, v, mesh, causal=True, inner="flash", inner_block_size=16
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_the_lines_the_mosaic_modules_record_have_not_moved() -> None:
    """The Mosaic module of each kernel holds source locations of this
    file: the ``where`` of the causal mask (under shard_map), the three
    ``pallas_call``s and ``flash_attention``'s call of the kernel. A line
    more or fewer ahead of any of them changes the lowered train step, and
    with it the compile-cache key, of every model that runs the kernels
    (PERF.md, PRs 32 and 34). Move them knowingly: re-base every cell."""
    import inspect

    from torchsnapshot_tpu.ops import pallas_attention as pa

    lines = inspect.getsource(pa).splitlines()
    at = lambda text: [i + 1 for i, line in enumerate(lines) if text in line]  # noqa: E731
    assert at("return jnp.where(q_pos >= k_pos, s, NEG_INF)") == [41]
    assert at("pl.pallas_call(") == [234, 260, 275]
    assert at("out = flash(qt, kt, vt)") == [373]
    assert at("    return jax.shard_map(") == [421]
