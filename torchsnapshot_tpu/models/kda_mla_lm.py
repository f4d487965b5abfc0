"""A hybrid linear-attention expert LM: Kimi Delta Attention layers, one
latent-attention layer a period, group-limited sigmoid routing.

The ``bailing_hybrid`` language model of Ling-3.0-flash-VL (inclusionAI;
its ``config.json``), as a train state on the same path as the other
families (``init_state`` / ``make_train_step`` / ``CheckpointManager``). The
config gives the widths, the counts, the layer period, the decay's lower
bound, the convolution's kernel, the latent ranks, the routing's groups and
the clamps; **the form of each mechanism is written from memory of the
papers and code, no network** (KDA: *Kimi Linear*, arXiv:2510.26692; latent
attention: DeepSeek-V2, arXiv:2405.04434; the group-limited sigmoid router
with a selection bias: DeepSeek-V3, arXiv:2412.19437), and
``benchmarks/chip/configs/ling3flash.json`` lists every such form under
``assumed``. With ``rms(x; w) = x rsqrt(mean(x^2) + eps) w`` in float32 on
the stream ``x: (B, S, D)``, published layer ``p`` is latent attention
(MLA) iff ``(p + 1) % layer_group_size == 0``, else KDA, and its MLP is
dense iff ``p < first_k_dense``::

    x = E[tokens]
    per layer:  x <- x + mixer(rms(x; g1));   x <- x + mlp(rms(x; g2))
    KDA   q, k, v = silu(conv4(a W_q)), silu(conv4(a W_k)), silu(conv4(a W_v))      depthwise, causal, no bias
          q <- q / |q| / sqrt(d_k),  k <- k / |k|  a head;   beta = sigmoid(a W_b)   one a head
          g = L sigmoid(exp(A_h) (a W_f + b_dt))                                     (-5, 0) a key channel
          o = kda(q, k, v, g, beta)                                                  ops/kda.py
          out = (rms_head(o; g_o) * sigmoid(a W_g)) W_o
    MLA   q = a W_q = [q_nope ; q_rope] a head;  [c ; k_r] = a W_kva;  c <- rms(c; g_c)
          [k_nope ; v] = c W_kvb a head;  k_h = [k_nope,h ; k_r]                     k_r shared by every head
          q, k <- rope(rms(. ; g_q / g_k)) over each head's 192, the last 64 rotated
          out = (softmax(q k^T / sqrt(192) + causal) v * sigmoid(a w_g,h)) W_o      a gate a head
    dense silu(b G) * (b U) D
    moe   s = sigmoid(b W_r) (float32);  the top_k of the topk_group best of n_group groups by s + bias
          w_i = scale s_i / sum of the chosen s
          out = sum over the chosen experts HELD HERE of w_i E_i(b) + E_shared(b)
          E(b) = (silu(min(b G, l)) * clip(b U, -l, l)) D,  l the layer's clamp (0: none)
    logits = rms(x; g_f) W_head^T (untied);  loss = mean next-token cross-entropy

**The chip's share of the experts** is ``hybrid_lm``'s: the layer is told
the ids it holds (``cfg.held``), scores and chooses over all ``n_experts``
and adds only its own experts' terms (``ops/moe.py``
``sigmoid_group_routed``; no token is dropped). **The vocabulary held
here** is a slice: ids are drawn from it, logits and loss are over it.
**What is not here** (the file's ``departures``): the multi-token
prediction module, the vision tower, an update rule for the selection bias
(held fixed), an auxiliary balancing loss, generation and the two caches
decoding would keep.

How it is compiled: the layers of one kind (mixer and MLP) are **stacked**
in one subtree, ``kda_dense``, ``kda_moe``, ``mla_moe``, and run in the
published order: a run of consecutive layers of one kind (and one clamp)
is a ``lax.scan`` over its slice of the stack, a run of one layer a call,
each layer a ``jax.checkpoint``. A KDA layer's checkpoint keeps the head
blocks' output (``kda_heads``, ``KDA_LAYER_POLICY``), so the layer's
recomputation does not run them: they run forward twice a step, in the
forward pass and under their own checkpoint in the backward pass; an MLA
layer's saves nothing. Head and loss run in blocks of
``head_block`` positions under ``jax.checkpoint``. The matrices are cast to
the compute dtype once a step and the train step differentiates that tree,
as ``looped_lm.py`` says; float32 stay the stream, the norms, the
convolutions, the decays and their sums, KDA's state and algebra, the
rotation, the router end to end, and every matmul result.

Sharding: the batch over 'data', ``embed`` and ``head`` over the vocabulary
on 'model', the layers' leaves replicated (no multi-chip cell runs this
family).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..ops import kda as kda_ops
from ..ops.moe import clamped_gated_ffn, gated_ffn, group_limited, held_tile_stats, sigmoid_group_routed
from . import kit
from .kit import causal_conv, constrainer, make_optimizer, mm, rmsnorm  # noqa: F401  (make_optimizer: the same optimizer)

Params = Dict[str, Any]
L2_FLOOR = 1e-6  # under the L2 norm of a head's q and k (flash-linear-attention's l2norm eps, assumed)


@dataclasses.dataclass(frozen=True)
class KDAMLALMConfig:
    """Published sizes (defaults: Ling-3.0-flash-VL's ``config.json``).
    ``layers`` are the published ids of the layers held here, in order;
    ``held`` the experts whose weights live here; ``expert_limits`` and
    ``shared_limits`` the clamp of each held layer (empty: none)."""

    vocab_size: int = 157184
    d_model: int = 2560
    layers: Tuple[int, ...] = tuple(range(42))
    layer_group_size: int = 6
    first_k_dense: int = 2
    n_heads: int = 32
    head_dim: int = 128  # KDA's d_k = d_v
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    chunk: int = 64
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    dense_ff: int = 6144
    n_experts: int = 512
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    expert_ff: int = 768
    shared_ff: int = 768
    routed_scale: float = 2.5
    held: Tuple[int, ...] = tuple(range(512))
    expert_limits: Tuple[float, ...] = ()
    shared_limits: Tuple[float, ...] = ()
    norm_eps: float = 1e-6
    head_block: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(set(self.held)) != len(self.held) or not all(0 <= e < self.n_experts for e in self.held):
            raise ValueError(f"held expert ids {self.held} are not distinct ids below {self.n_experts}")
        if list(self.layers) != sorted(set(self.layers)) or not self.layers:
            raise ValueError(f"layer ids {self.layers} are not distinct and in order")
        for limits in (self.expert_limits, self.shared_limits):
            if limits and len(limits) != len(self.layers):
                raise ValueError(f"{len(limits)} clamp limits for {len(self.layers)} layers")
        if self.n_experts % self.n_group or (self.n_experts // self.n_group) * self.topk_group < self.top_k:
            raise ValueError(f"top-{self.top_k} of {self.topk_group} of {self.n_group} groups of {self.n_experts}")

    @property
    def kinds(self) -> List[str]:
        """Each held layer's stack: ``<mixer>_<mlp>``."""
        return [
            ("mla" if (p + 1) % self.layer_group_size == 0 else "kda") + ("_dense" if p < self.first_k_dense else "_moe")
            for p in self.layers
        ]

    def limits(self, i: int) -> Tuple[float, float]:
        """Held layer ``i``'s clamps, experts' and shared expert's."""
        return tuple(float(lim[i]) if lim else 0.0 for lim in (self.expert_limits, self.shared_limits))

    @property
    def runs(self) -> List[Tuple[str, int, int, Tuple[float, float]]]:
        """Consecutive held layers of one kind and clamp: ``(stack, first,
        end, limits)``, ``first`` and ``end`` indices into the stack."""
        out, seen = [], {}
        for key, group in itertools.groupby(range(len(self.layers)), lambda i: (self.kinds[i], self.limits(i))):
            n = len(list(group))
            first = seen.get(key[0], 0)
            out.append((key[0], first, first + n, key[1]))
            seen[key[0]] = first + n
        return out

    @property
    def stack_sizes(self) -> Dict[str, int]:
        return {k: self.kinds.count(k) for k in dict.fromkeys(self.kinds)}

    @property
    def mla_qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    def layer_matmul_params(self, kind: str) -> int:
        """Parameters one position multiplies by in a layer of ``kind``:
        every projection, and of the held experts their expected share
        under even routing (``top_k * len(held) / n_experts`` of one)."""
        D, H, hd = self.d_model, self.n_heads, self.head_dim
        mixer = {
            "kda": 6 * D * H * hd + D * H,
            "mla": D * H * self.mla_qk_dim + D * (self.kv_lora_rank + self.qk_rope_dim)
            + self.kv_lora_rank * H * (self.qk_nope_dim + self.v_head_dim) + D * H + H * self.v_head_dim * D,
        }[kind.split("_")[0]]
        if kind.endswith("_dense"):
            return mixer + 3 * D * self.dense_ff
        routed = round(self.top_k * len(self.held) / self.n_experts * 3 * D * self.expert_ff)
        return mixer + D * self.n_experts + 3 * D * self.shared_ff + routed

    @property
    def matmul_params_per_token(self) -> int:
        return sum(self.layer_matmul_params(k) for k in self.kinds) + self.vocab_size * self.d_model

    def saved_heads_bytes(self, batch: int, seq: int) -> int:
        """Bytes the KDA layers' checkpoints keep for a step of ``(batch,
        seq)``: each KDA layer's head-block output, ``(B, S, H d)`` in the
        compute dtype."""
        n_kda = sum(k.startswith("kda") for k in self.kinds)
        return n_kda * batch * seq * self.n_heads * self.head_dim * jnp.dtype(self.dtype).itemsize

    def kda_kernel_layers(self, seq: int) -> int:
        """KDA layers whose chunk algebra runs in the Pallas kernels at
        ``seq`` positions (``ops/kda.py`` ``kda_route``): all or none."""
        n_kda = sum(k.startswith("kda") for k in self.kinds)
        return n_kda if kda_ops.kda_route(seq, self.head_dim, self.head_dim, self.chunk) == "pallas" else 0


# The residual branches write small: every last matrix of a branch (KDA's
# and MLA's o, the MLPs' down) at 0.3 of fan_in^-0.5, times the embedding's
# D^-0.5, as cca_moe_lm's (PERF.md, PRs 34, 38). At full scale each branch
# writes 25 x the embedding's size into the stream, a stack of bfloat16
# products then differs from float32 by 3 % of the logits' largest at the
# CPU rehearsal's widths (0.3 % with this), and the routers see mostly what
# the layers before wrote, not their own token.
_BRANCH_OUT_SCALE = 0.3


def _init_stack(key: jax.Array, kind: str, L: int, cfg: KDAMLALMConfig) -> Params:
    """One stack's leaves, ``(L, ...)`` each. Matrices normal with std
    ``fan_in^-0.5``, norm scales 1; KDA's convolutions ``U(-1/2, 1/2)``
    (PyTorch's default for a depthwise kernel of 4), ``A_log = log U(1,
    16)`` a head and ``b_dt`` the inverse softplus of ``exp(U(log 1e-3,
    log 1e-1))`` (Mamba's, as flash-linear-attention's KDA takes them); the
    router normal, its selection bias normal with std 0.01 (small beside
    the scores' spread, as ``hybrid_lm``'s: PERF.md, PR 32)."""
    c, dt = cfg, cfg.param_dtype
    D, H, hd = c.d_model, c.n_heads, c.head_dim
    A = H * hd
    keys = iter(jax.random.split(key, 24))
    out = _BRANCH_OUT_SCALE * D**-0.5

    def norm(shape, fan_in, scale=1.0):
        return jax.random.normal(next(keys), (L, *shape), dt) * (scale * fan_in**-0.5)

    def ones(*shape):
        return jnp.ones((L, *shape), dt)

    mixer, mlp = kind.split("_")
    w = {"ln1_scale": ones(D), "ln2_scale": ones(D)}
    if mixer == "kda":
        step = jnp.exp(jax.random.uniform(next(keys), (L, A), jnp.float32, math.log(1e-3), math.log(1e-1)))
        w.update(
            q=norm((D, A), D), k=norm((D, A), D), v=norm((D, A), D), f=norm((D, A), D), g=norm((D, A), D),
            b=norm((D, H), D), o=norm((A, D), A, out),
            q_conv=jax.random.uniform(next(keys), (L, c.conv_kernel, A), dt, -0.5, 0.5),
            k_conv=jax.random.uniform(next(keys), (L, c.conv_kernel, A), dt, -0.5, 0.5),
            v_conv=jax.random.uniform(next(keys), (L, c.conv_kernel, A), dt, -0.5, 0.5),
            A_log=jnp.log(jax.random.uniform(next(keys), (L, H), jnp.float32, 1.0, 16.0)).astype(dt),
            dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(dt),  # softplus^-1(step)
            o_norm=ones(hd),
        )
    else:
        R, qk = c.kv_lora_rank, c.mla_qk_dim
        w.update(
            q=norm((D, H * qk), D), kv_a=norm((D, R + c.qk_rope_dim), D), kv_a_norm=ones(R),
            kv_b=norm((R, H * (c.qk_nope_dim + c.v_head_dim)), R), q_norm=ones(qk), k_norm=ones(qk),
            gate=norm((D, H), D), o=norm((H * c.v_head_dim, D), H * c.v_head_dim, out),
        )
    if mlp == "dense":
        w.update(mlp_gate=norm((D, c.dense_ff), D), mlp_up=norm((D, c.dense_ff), D),
                 mlp_down=norm((c.dense_ff, D), c.dense_ff, out))
    else:
        n, F, Fs = len(c.held), c.expert_ff, c.shared_ff
        w.update(
            router=norm((D, c.n_experts), D),
            router_bias=jax.random.normal(next(keys), (L, c.n_experts), dt) * 0.01,
            expert_gate=norm((n, D, F), D), expert_up=norm((n, D, F), D), expert_down=norm((n, F, D), F, out),
            shared_gate=norm((D, Fs), D), shared_up=norm((D, Fs), D), shared_down=norm((Fs, D), Fs, out),
        )
    return w


def init_params(rng: jax.Array, cfg: KDAMLALMConfig) -> Params:
    """The parameter pytree: one stacked subtree a kind of layer."""
    k_embed, k_head, k_layers = jax.random.split(rng, 3)
    D = cfg.d_model
    stacks = {
        kind: _init_stack(jax.random.fold_in(k_layers, i), kind, L, cfg)
        for i, (kind, L) in enumerate(cfg.stack_sizes.items())
    }
    return {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, D), cfg.param_dtype) * D**-0.5,
        "head": jax.random.normal(k_head, (cfg.vocab_size, D), cfg.param_dtype) * D**-0.5,
        "layers": stacks,
        "ln_f_scale": jnp.ones((D,), cfg.param_dtype),
    }


def param_specs(cfg: KDAMLALMConfig) -> Params:
    """PartitionSpecs on a ('data','model') mesh: ``embed`` and ``head`` over
    the vocabulary, every layer leaf replicated (module docstring)."""
    return kit.vocab_sharded(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))


# Leaves that feed a matmul in the compute dtype. The router's product is
# float32 at full precision and the convolutions are elementwise: they stay.
_MATRICES = {"q", "k", "v", "f", "g", "b", "o", "kv_a", "kv_b", "gate", "mlp_gate", "mlp_up", "mlp_down",
             "expert_gate", "expert_up", "expert_down", "shared_gate", "shared_up", "shared_down"}


def compute_params(params: Params, cfg: KDAMLALMConfig) -> Params:
    """The tree the layers read: every matrix that feeds a matmul (and the
    head) in the compute dtype, cast once a step; the embedding, the
    scales, the convolutions, the per-head scalars and the router as stored."""
    return kit.cast_matrices(params, cfg.dtype, _MATRICES)


def _unit(x: jax.Array) -> jax.Array:
    """``x / |x|`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_FLOOR**2)


_KDA_COLUMNS = {"q", "k", "v", "f", "g", "q_conv", "k_conv", "v_conv", "dt_bias", "b", "A_log"}  # cut by head
# Heads of a pass of the KDA mixer: at the cell's B 1 x S 8192, 8 at a time
# plan 13.26 GB of a chip's 15.75 with the chunk algebra's Pallas kernels
# (XLA's form: 12.81; 32 at once 17.28, 4 at a time 13.00; PERF.md §4).
KDA_HEAD_BLOCK = 8
# What a KDA layer's checkpoint keeps for the backward pass: the head
# blocks' output alone. Module-level so that tests can swap it.
KDA_LAYER_POLICY = jax.checkpoint_policies.save_only_these_names("kda_heads")


def kda_mixer(w: Params, a: jax.Array, cfg: KDAMLALMConfig) -> jax.Array:
    """The KDA mixer on the normed stream ``a: (B, S, D)`` -> float32.
    Everything ahead of ``W_o`` is a head's own, so it runs
    ``KDA_HEAD_BLOCK`` heads at a time (all of them where there are no
    more), each block under ``jax.checkpoint``: the backward pass holds one
    block's projections, convolutions and chunk algebra (a dozen float32
    tensors of ``(B, S, h, d)``) and computes that block's forward once
    more. Their output, in the compute dtype ``W_o`` reads, is named
    ``kda_heads``: the layer's checkpoint keeps it, so the blocks run
    forward twice a step, not three times."""
    c = cfg
    B, S, _ = a.shape
    H = c.n_heads
    h = min(KDA_HEAD_BLOCK, H)
    a = a.astype(c.dtype)
    cut = lambda t: jnp.moveaxis(t.reshape(*t.shape[:-1], H // h, -1), -2, 0)  # noqa: E731  (.., H x) -> (H/h, .., h x)
    blocks = {k: cut(v) for k, v in w.items() if k in _KDA_COLUMNS}
    o = jax.lax.map(jax.checkpoint(lambda wb: _kda_heads({**wb, "o_norm": w["o_norm"]}, a, c)), blocks)
    o = checkpoint_name(jnp.moveaxis(o, 0, 2).reshape(B, S, H * c.head_dim).astype(c.dtype), "kda_heads")
    with jax.named_scope("kda_out"):
        return mm(o, w["o"])


def _kda_heads(w: Params, a: jax.Array, cfg: KDAMLALMConfig) -> jax.Array:
    """The heads ``w``'s columns hold, up to ``W_o``: ``(B, S, h d)`` float32."""
    c, f32 = cfg, jnp.float32
    B, S, _ = a.shape
    H, hd = w["b"].shape[-1], c.head_dim
    heads = lambda t: t.reshape(B, S, H, hd)  # noqa: E731
    with jax.named_scope("kda_proj"):
        q, k, v = (mm(a, w[n]) for n in ("q", "k", "v"))
    with jax.named_scope("kda_conv"):
        q, k, v = (jax.nn.silu(causal_conv(t, w[f"{n}_conv"], jnp.zeros((), f32))) for t, n in ((q, "q"), (k, "k"), (v, "v")))
        q, k = _unit(heads(q)) * hd**-0.5, _unit(heads(k))
    with jax.named_scope("kda_gate"):
        beta = jax.nn.sigmoid(mm(a, w["b"]))  # (B, S, H)
        rate = jnp.repeat(jnp.exp(w["A_log"].astype(f32)), hd)  # A_h over the head's channels
        g = c.kda_lower_bound * jax.nn.sigmoid(rate * (mm(a, w["f"]) + w["dt_bias"].astype(f32)))
    o = kda_ops.kda(q, k, heads(v), heads(g), beta, chunk=c.chunk)
    with jax.named_scope("kda_out"):
        return rmsnorm(o, w["o_norm"], c.norm_eps).reshape(B, S, H * hd) * jax.nn.sigmoid(mm(a, w["g"]))


def _rope_tail(x: jax.Array, rot: int, theta: float) -> jax.Array:
    """Rotary positions 0 .. S-1 on the last ``rot`` of ``(B, S, H, d)``,
    half-split pairs ``(i, i + rot / 2)``; float32."""
    S = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    keep, x1, x2 = jnp.split(x, [x.shape[-1] - rot, x.shape[-1] - rot // 2], axis=-1)
    return jnp.concatenate([keep, x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla_mixer(w: Params, a: jax.Array, cfg: KDAMLALMConfig, attend: Callable) -> jax.Array:
    """The latent-attention mixer -> float32. q·k is ``qk_nope + qk_rope``
    wide and v ``v_head_dim``: v goes to the kernel padded with zeros to
    q's width (``attend`` takes one width) and the pad is cut off after."""
    c = cfg
    B, S, _ = a.shape
    H, R, nope, rope, dv = c.n_heads, c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
    with jax.named_scope("mla_proj"):
        q = mm(a, w["q"]).reshape(B, S, H, nope + rope)
        latent = rmsnorm(mm(a, w["kv_a"][:, :R]), w["kv_a_norm"], c.norm_eps)  # the weight cut, not the product
        k_r = mm(a, w["kv_a"][:, R:])  # (B, S, rope): one for every head
        kv_b = w["kv_b"].reshape(R, H, nope + dv)
        lat = latent.astype(c.dtype)
        k_nope = jnp.einsum("bsr,rhd->bshd", lat, kv_b[..., :nope], preferred_element_type=jnp.float32)
        v = jnp.einsum("bsr,rhd->bshd", lat, kv_b[..., nope:], preferred_element_type=jnp.float32)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r[:, :, None], (B, S, H, rope))], axis=-1)
        q = _rope_tail(rmsnorm(q, w["q_norm"], c.norm_eps), rope, c.rope_theta)
        k = _rope_tail(rmsnorm(k, w["k_norm"], c.norm_eps), rope, c.rope_theta)
    with jax.named_scope("mla_attn"):
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nope + rope - dv)))
        out = attend(q.astype(c.dtype), k.astype(c.dtype), v.astype(c.dtype))[..., :dv]
    with jax.named_scope("mla_gate"):
        out = out.astype(jnp.float32) * jax.nn.sigmoid(mm(a, w["gate"]))[..., None]
        return mm(out.reshape(B, S, H * dv), w["o"])


def moe_mlp(w: Params, b: jax.Array, cfg: KDAMLALMConfig, limits: Tuple[float, float]):
    """The routed MLP -> (float32 output, the chosen expert ids (T, k))."""
    c = cfg
    routed, ids = sigmoid_group_routed(
        w, b, top_k=c.top_k, held=c.held, routed_scale=c.routed_scale, n_group=c.n_group,
        topk_group=c.topk_group, limit=limits[0],
    )
    with jax.named_scope("moe_shared"):
        return routed + clamped_gated_ffn(b, w["shared_gate"], w["shared_up"], w["shared_down"], limits[1]), ids


def _held_groups_kept(w: Params, b: jax.Array, cfg: KDAMLALMConfig) -> jax.Array:
    """``(T,)``: whether a token's kept groups include every group that
    holds an expert held here (``routing_stats`` only: a second pass over
    the router's product)."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.matmul(b.reshape(-1, b.shape[-1]), w["router"].astype(f32),
                                  precision=jax.lax.Precision.HIGHEST))
    kept = jnp.isfinite(group_limited(s + w["router_bias"].astype(f32), cfg.n_group, cfg.topk_group))
    return jnp.all(kept[:, jnp.asarray(cfg.held)], axis=-1)


def layer(w: Params, x: jax.Array, kind: str, cfg: KDAMLALMConfig, attend: Callable,
          limits: Tuple[float, float], stats: bool = False):
    """One layer on the float32 stream -> (the new stream, the chosen ids
    ``(T, k)`` of an expert layer, else None; with ``stats``, the ids and
    ``_held_groups_kept``)."""
    c = cfg
    mixer, mlp = kind.split("_")
    a = rmsnorm(x, w["ln1_scale"], c.norm_eps)
    x = x + (kda_mixer(w, a, c) if mixer == "kda" else mla_mixer(w, a, c, attend))
    b = rmsnorm(x, w["ln2_scale"], c.norm_eps)
    if mlp == "dense":
        with jax.named_scope("dense_mlp"):
            return x + gated_ffn(b, w["mlp_gate"], w["mlp_up"], w["mlp_down"]), None
    y, ids = moe_mlp(w, b, c, limits)
    return x + y, ((ids, _held_groups_kept(w, b, c)) if stats else ids)


def select_attention(cfg: KDAMLALMConfig, mesh: Optional[Mesh], B: int, S: int) -> str:
    """The name of the attention route the MLA layers run for this mesh and shape."""
    return kit.route_name(cfg.n_heads, mesh, B, S)


def _run_layers(cparams: Params, tokens: jax.Array, cfg: KDAMLALMConfig, mesh: Optional[Mesh], stats: bool = False):
    """The closed hidden state ``rms(x; g_f)`` ``(B, S, D)`` in float32 and,
    per expert layer in order, the chosen ids (with ``stats``, the ids and
    ``_held_groups_kept``). ``cparams`` is ``compute_params``' tree."""
    B, S = tokens.shape
    cs = constrainer(mesh)
    stream = P("data", None, None)
    _, attend = kit.attention_route(cfg.n_heads, mesh, B, S)
    x = cs(cparams["embed"][tokens].astype(jnp.float32), stream)
    chosen = []
    for kind, first, end, limits in cfg.runs:

        @functools.partial(jax.checkpoint, policy=KDA_LAYER_POLICY if kind.startswith("kda") else None)
        def one(x, w, kind=kind, limits=limits):
            x, ids = layer(w, x, kind, cfg, attend, limits, stats)
            return cs(x, stream), ids

        stack = cparams["layers"][kind]
        if end - first == 1:
            x, ids = one(x, jax.tree_util.tree_map(lambda t: t[first], stack))
            ids = None if ids is None else jax.tree_util.tree_map(lambda t: t[None], ids)
        else:
            x, ids = jax.lax.scan(one, x, jax.tree_util.tree_map(lambda t: t[first:end], stack))
        if ids is not None:
            chosen += [jax.tree_util.tree_map(lambda t: t[i], ids) for i in range(end - first)]
    return rmsnorm(x, cparams["ln_f_scale"], cfg.norm_eps), chosen


def forward(params: Params, tokens: jax.Array, cfg: KDAMLALMConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    """(B, S) int32 tokens -> (B, S, vocab) float32 logits."""
    p = compute_params(params, cfg)
    return kit.head(_run_layers(p, tokens, cfg, mesh)[0], p["head"], constrainer(mesh))


def chosen_experts(params: Params, tokens: jax.Array, cfg: KDAMLALMConfig) -> List[jax.Array]:
    """Per expert layer in order, the ids ``(T, top_k)`` each position
    chose, from the forward pass the train step runs."""
    return _run_layers(compute_params(params, cfg), tokens, cfg, None)[1]


def routing_stats(params: Params, tokens: jax.Array, cfg: KDAMLALMConfig) -> Dict[str, jax.Array]:
    """What the routers did with this batch, ``(L_moe,)`` a statistic:
    ``held_share``, the share of the ``top_k * T`` assignments that fall on
    experts held here (``len(held) / n_experts`` under even routing);
    ``group_share``, the share of tokens whose kept groups include the
    held experts' (``topk_group / n_group`` under even routing, where they
    lie in one group); ``max_over_mean``, the most positions a held expert
    gets over their mean; ``held_counts`` ``(L_moe, n)``; and the loops'
    ``trips`` and ``tile_fill`` (``ops/moe.py`` ``held_tile_stats``)."""
    out = _run_layers(compute_params(params, cfg), tokens, cfg, None, stats=True)[1]
    ids = jnp.stack([i for i, _ in out])  # (L_moe, T, k)
    held = jnp.asarray(cfg.held, jnp.int32)
    counts = jnp.sum(ids[:, None] == held[None, :, None, None], axis=(2, 3))  # (L_moe, n)
    return {
        "held_counts": counts,
        "held_share": jnp.sum(counts, axis=1) / (ids.shape[1] * ids.shape[2]),
        "group_share": jnp.stack([jnp.mean(kept.astype(jnp.float32)) for _, kept in out]),
        "max_over_mean": jnp.max(counts, axis=1) / jnp.maximum(jnp.mean(counts.astype(jnp.float32), axis=1), 1e-9),
        **held_tile_stats(counts, ids.shape[1]),
    }


def _objective(cparams: Params, batch: Dict[str, jax.Array], cfg: KDAMLALMConfig, mesh: Optional[Mesh]):
    h, _ = _run_layers(cparams, batch["tokens"], cfg, mesh)
    cs = constrainer(mesh)
    return kit.blocked_cross_entropy(h, cparams["head"], batch["targets"], cfg.head_block, lambda h, w: kit.head(h, w, cs))


def loss_fn(
    params: Params, batch: Dict[str, jax.Array], cfg: KDAMLALMConfig, *, mesh: Optional[Mesh] = None
) -> jax.Array:
    """Mean next-token cross-entropy over the vocabulary held here. No
    auxiliary loss: the config gives no coefficient."""
    return _objective(compute_params(params, cfg), batch, cfg, mesh)


def state_specs(cfg: KDAMLALMConfig, state: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_state``'s output (``kit.state_specs``)."""
    return kit.state_specs(param_specs(cfg), state)


def init_state(
    rng: jax.Array, cfg: KDAMLALMConfig, tx: optax.GradientTransformation, *, mesh: Optional[Mesh] = None
) -> Dict[str, Any]:
    """{params, opt_state, step}, the whole of it placed per ``state_specs``
    under a mesh."""
    return kit.init_state(init_params(rng, cfg), param_specs(cfg), tx, mesh)


def make_train_step(
    cfg: KDAMLALMConfig, tx: optax.GradientTransformation, *, mesh: Optional[Mesh] = None
) -> Callable:
    """Returns train_step(state, batch) -> (state, loss), ready to jit
    (``kit.make_train_step``). The selection bias is held fixed: it takes no
    gradient, its balancing update has no rate in the config, and adamw's
    weight decay would otherwise shrink it."""
    gauges = {
        "kda_mla_lm.layers": len(cfg.layers),
        "kda_mla_lm.experts_held": len(cfg.held),
        "kda_mla_lm.matmul_params_per_token": cfg.matmul_params_per_token,
    }

    def objective_args(state, batch):
        # Set as the step is traced, where the batch's shape is first known.
        telemetry.gauge_set("kda_mla_lm.saved_heads_bytes", cfg.saved_heads_bytes(*batch["tokens"].shape))
        telemetry.gauge_set("kda_mla_lm.kda_kernel_layers", cfg.kda_kernel_layers(batch["tokens"].shape[1]))
        return batch, cfg, mesh

    return kit.make_train_step(
        _objective, lambda p: compute_params(p, cfg), objective_args, tx, mesh,
        param_specs(cfg), gauges, frozen=("router_bias",),
    )
