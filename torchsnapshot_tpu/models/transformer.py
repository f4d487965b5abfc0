"""Flagship model: a GSPMD-sharded decoder-only transformer.

The reference (torchsnapshot) ships no model code — its benchmarks build
throwaway torch models (benchmarks/fsdp/main.py builds a 1.9B-param
transformer, benchmarks/ddp/main.py a 200x100MB-param module) purely to
produce realistic distributed state to checkpoint. This module is the
TPU-native analogue: a pure-JAX decoder-only transformer whose parameters
and training step are annotated for a ('data','model') mesh:

- dp: batch sharded over 'data'; each matrix's gradient crosses 'data'
  once a step, after the backward scan (the replica dimension in
  ``forward``)
- tp: hidden/ffn/vocab dims sharded over 'model' (Megatron-style
  column->row parallel pairs; XLA inserts the all-reduces)
- sp: the residual stream between blocks is sequence-sharded over 'model'
  (Megatron sequence parallelism), so norm/elementwise work is partitioned
  and XLA materializes all-gather/reduce-scatter at block boundaries.
- cp: with ``attn_impl="ring"`` and a mesh that has a 'seq' axis, the
  sequence dimension stays sharded end-to-end (context parallelism):
  attention runs as ring attention over the 'seq' axis (K/V rotate on the
  ICI ring, ops/ring_attention.py) and no full-sequence activation is ever
  gathered — the long-context configuration.

The state it produces (params + optax opt_state + step + PRNG key) is the
canonical AppState the snapshot layer checkpoints and reshards.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import CP_ROUTES, causal_attention_route
from ..parallel.mesh import data_replicas, with_replica_dim
from . import kit
from .kit import make_optimizer  # noqa: F401  (the zoo's optimizer)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # "auto" (flash on TPU; blockwise off-TPU for long seq; dense for
    # short) | "dense" | "blockwise" (pure-JAX online-softmax scan) |
    # "flash" (Pallas TPU kernel) | "ring" | "zigzag" | "ulysses" (context
    # parallel; these need a mesh with a 'seq' axis — ring/zigzag rotate
    # K/V on the ICI ring, ulysses all-to-alls seq<->head sharding).
    attn_impl: str = "auto"
    attn_block_size: int = 512
    # n_experts > 0 swaps the dense FFN for a top-2 MoE (ops/moe.py) with
    # expert weights sharded over the 'model' axis — expert parallelism.
    n_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def param_count(self) -> int:
        c = self
        per_layer = 4 * c.d_model * c.d_model + 2 * c.d_model * c.d_ff + 2 * c.d_model
        return c.vocab_size * c.d_model + c.n_layers * per_layer + c.d_model


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """Initialize the parameter pytree (stacked-layer layout).

    Per-layer weights are stacked along a leading layer axis so the forward
    pass is a single `lax.scan` over layers — one compiled block instead of
    n_layers unrolled ones, which keeps compile time flat as depth grows.
    """
    c = cfg
    k_embed, k_attn, k_o, k_ff1, k_ff2 = jax.random.split(rng, 5)
    L, D, F = c.n_layers, c.d_model, c.d_ff

    def norm(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, c.param_dtype) * (fan_in**-0.5)
        )

    layers: Dict[str, Any] = {
        "attn_qkv": norm(k_attn, (L, D, 3 * D), D),
        "attn_out": norm(k_o, (L, D, D), D),
        "ln1_scale": jnp.ones((L, D), c.param_dtype),
        "ln2_scale": jnp.ones((L, D), c.param_dtype),
    }
    if c.n_experts > 0:
        E = c.n_experts
        k_r, k_ff1, k_ff2 = jax.random.split(k_ff1, 3)
        layers["moe_router"] = norm(k_r, (L, D, E), D)
        layers["moe_w_in"] = norm(k_ff1, (L, E, D, F), D)
        layers["moe_w_out"] = norm(k_ff2, (L, E, F, D), F)
    else:
        layers["ff_in"] = norm(k_ff1, (L, D, F), D)
        layers["ff_out"] = norm(k_ff2, (L, F, D), F)
    return {
        "embed": norm(k_embed, (c.vocab_size, D), D),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), c.param_dtype),
    }


def param_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpecs for each param on a ('data','model') mesh (tp layout).

    Column-parallel (output dim on 'model'): qkv, ff_in.
    Row-parallel (input dim on 'model'): attn_out, ff_out.
    Norm scales replicated.

    The tied embedding (vocab, D) shards the vocabulary, dim 0: the head
    ``x @ embed.T`` is then a local matmul onto vocabulary-sharded logits
    (what the sharded log-softmax in ``loss_fn`` reduces over with two
    (B, S) reductions), and the lookup is a masked local gather plus one
    (B, S, D) reduction. Sharding D instead makes the head contract over
    a sharded dimension: full-vocabulary partial logits, all-reduced and
    gathered again in the backward pass. Needs ``vocab_size %
    mesh.shape["model"] == 0``; ``shard_pytree`` refuses placement
    otherwise.
    """
    layers = {
        "attn_qkv": P(None, None, "model"),
        "attn_out": P(None, "model", None),
        "ln1_scale": P(None, None),
        "ln2_scale": P(None, None),
    }
    if cfg.n_experts > 0:
        # ep: the expert dimension shards over 'model' (router replicated).
        layers["moe_router"] = P(None, None, None)
        layers["moe_w_in"] = P(None, "model", None, None)
        layers["moe_w_out"] = P(None, "model", None, None)
    else:
        layers["ff_in"] = P(None, None, "model")
        layers["ff_out"] = P(None, "model", None)
    return {
        "embed": P("model", None),
        "layers": layers,
        "ln_f_scale": P(None),
    }


def _rmsnorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6).astype(x.dtype)) * scale.astype(x.dtype)


def _attention_route(cfg: TransformerConfig, mesh: Optional[Mesh], B: int, S: int):
    return causal_attention_route(
        cfg.attn_impl, cfg.attn_block_size, cfg.n_heads, mesh, B, S
    )


def select_attention(
    cfg: TransformerConfig, mesh: Optional[Mesh], B: int, S: int
) -> str:
    """The attention path ``forward`` runs for this config, mesh and shape:
    the name of the route ``ops.attention.causal_attention_route`` selects
    (that function is the whole decision, shared with the other models)."""
    return _attention_route(cfg, mesh, B, S)[0]


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    with_aux: bool = False,
):
    """Causal LM forward: (batch, seq) int32 -> (batch, seq, vocab) logits.

    When `mesh` is given, sharding constraints implement dp/tp/sp; with
    mesh=None the same code runs single-device. With ``with_aux=True``
    returns (logits, aux_loss) — the MoE load-balancing term (0 for dense
    FFN configs).
    """
    c = cfg
    B, S = tokens.shape

    def cs(x, spec):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    impl, attend = _attention_route(c, mesh, B, S)
    # cp (ring/ulysses) keeps the sequence dim sharded over 'seq' end-to-end;
    # the Megatron-sp fallback seq-shards the residual over the tp axis
    # instead and gathers around attention/ffn.
    has_seq = mesh is not None and "seq" in mesh.axis_names
    cp = impl in CP_ROUTES
    res_seq_ax = "seq" if has_seq else "model"  # residual-stream seq sharding
    act_seq_ax = "seq" if cp else None  # in-block activation seq sharding

    layers, embed = params["layers"], params["embed"]
    R = data_replicas(mesh, B)

    def mm(h, w):
        """(B, S, K) times a matrix, or replica by replica times its R copies."""
        w = w.astype(c.dtype)
        if w.ndim == 2:
            return h @ w
        y = jnp.einsum("rbsk,rkn->rbsn", h.reshape(R, B // R, S, -1), w)
        return y.reshape(B, S, -1)

    if R > 1:
        # dp: every replica multiplies by its own copy of each matrix (the
        # replica dimension: sharded over 'data', so a view of the shard a
        # device already holds). A matrix's gradient then stays local
        # through the backward scan, the tied embedding's two contributions
        # (lookup and head) add up locally, and the sum over that dimension,
        # the only gradient traffic over 'data', runs once per leaf after
        # the scan, on the stacked (L, ...) gradient. Cast first: the sum
        # runs in the dtype that is broadcast. The norm scales (two
        # D-vectors a layer) and the MoE leaves (``moe_ffn`` takes no
        # replica dimension) stay on the plain path: reduced where each
        # use produces them.
        p_specs = param_specs(c)
        layers = dict(layers)
        for name in ("attn_qkv", "attn_out", "ff_in", "ff_out"):
            if name in layers:
                layers[name] = with_replica_dim(
                    layers[name].astype(c.dtype), p_specs["layers"][name], mesh, dim=1
                )
        embed = with_replica_dim(embed.astype(c.dtype), p_specs["embed"], mesh)
        x = jax.vmap(lambda e, t: e[t])(embed, tokens.reshape(R, B // R, S))
        x = x.reshape(B, S, c.d_model)
    else:
        x = embed.astype(c.dtype)[tokens]  # (B, S, D)
    pos = jnp.arange(S)[None, :, None]
    dims = jnp.arange(c.d_model // 2)[None, None, :]
    inv_freq = 10000.0 ** (-2.0 * dims / c.d_model)
    # Fixed sinusoidal position encoding added to embeddings.
    angles = pos * inv_freq
    pe = jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)
    x = x + pe.astype(c.dtype)

    # Zigzag context parallelism: apply the folded layout ONCE here and
    # invert it once at the logits — attention runs in-layout, so the 2
    # permutes per layer the naive integration would pay collapse to 2 per
    # forward. Valid only while everything between commutes with the
    # permutation: true for the dense FFN (position-wise), NOT for MoE,
    # whose capacity overflow drops tokens in token order — hoisting would
    # make training numerics depend on the parallelism layout. MoE configs
    # therefore keep the per-layer permuting wrapper.
    zz_hoist = impl in ("zigzag", "zigzag_flash") and c.n_experts == 0
    if zz_hoist:
        from ..ops.ring_attention import zigzag_layout_indices

        zz_idx = zigzag_layout_indices(S, mesh.shape["seq"])
        zz_inv = jnp.argsort(zz_idx)
        x = jnp.take(x, zz_idx, axis=1)

    def block(carry, layer):
        x, aux = carry
        x = cs(x, P("data", res_seq_ax, None))
        h = _rmsnorm(x, layer["ln1_scale"])
        h = cs(h, P("data", act_seq_ax, None))
        qkv = mm(h, layer["attn_qkv"])  # (B,S,3D)
        qkv = cs(qkv, P("data", act_seq_ax, "model"))
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            t = t.reshape(B, S, c.n_heads, c.head_dim)
            return cs(t, P("data", act_seq_ax, "model", None))

        # (B,S,H,hd) — logical shapes; sharding via constraints.
        attn = attend(heads(q), heads(k), heads(v), in_layout=zz_hoist)
        attn = attn.reshape(B, S, c.d_model)
        attn = cs(attn, P("data", act_seq_ax, "model"))
        x = x + cs(mm(attn, layer["attn_out"]), P("data", res_seq_ax, None))

        h = _rmsnorm(x, layer["ln2_scale"])
        if c.n_experts > 0:
            from ..ops.moe import moe_ffn

            h = cs(h, P("data", act_seq_ax, None))
            y, l_aux = moe_ffn(
                {
                    "router": layer["moe_router"],
                    "w_in": layer["moe_w_in"],
                    "w_out": layer["moe_w_out"],
                },
                h,
                capacity_factor=c.moe_capacity_factor,
            )
            x = x + cs(y, P("data", res_seq_ax, None))
            aux = aux + l_aux
        else:
            h = cs(h, P("data", act_seq_ax, None))
            h = jax.nn.gelu(mm(h, layer["ff_in"]))
            h = cs(h, P("data", act_seq_ax, "model"))
            x = x + cs(mm(h, layer["ff_out"]), P("data", res_seq_ax, None))
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(block, (x, jnp.zeros((), jnp.float32)), layers)
    x = cs(x, P("data", act_seq_ax, None))
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = mm(x, jnp.swapaxes(embed.astype(c.dtype), -1, -2))
    if zz_hoist:
        logits = jnp.take(logits, zz_inv, axis=1)  # back to global order
    logits = cs(logits, P("data", act_seq_ax, "model"))
    if with_aux:
        return logits, aux
    return logits


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    logits, aux = forward(params, batch["tokens"], cfg, mesh=mesh, with_aux=True)
    targets = batch["targets"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll) + cfg.moe_aux_weight * aux


def make_train_step(
    cfg: TransformerConfig,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Callable:
    """Returns train_step(state, batch) -> (state, loss), ready to jit.

    state = {"params": ..., "opt_state": ..., "step": int32 scalar}.
    """

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(
            state["params"], batch, cfg, mesh=mesh
        )
        updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        if mesh is not None:
            # Pin the state the step returns to the layout init_state
            # placed it in. Left to GSPMD, replicated leaves come back
            # sharded (ln_f_scale over 'model'): step 2 then recompiles
            # for the drifted input, and what gets saved is no longer
            # the layout state_specs declares.
            new_state = jax.tree_util.tree_map(
                lambda x, spec: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, spec)
                ),
                new_state,
                state_specs(cfg, new_state),
            )
        return new_state, loss

    return train_step


def init_state(
    rng: jax.Array,
    cfg: TransformerConfig,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Dict[str, Any]:
    """Initialize {params, opt_state, step}; the whole of it placed per
    ``state_specs`` under a mesh, scalars too (``kit`` says why)."""
    return kit.init_state(init_params(rng, cfg), param_specs(cfg), tx, mesh)


def state_specs(cfg: TransformerConfig, state: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching init_state's output (``kit.state_specs``)."""
    return kit.state_specs(param_specs(cfg), state)
