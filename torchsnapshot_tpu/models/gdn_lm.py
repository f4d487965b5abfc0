"""A hybrid linear-attention expert LM: Gated DeltaNet layers, one gated
full-attention layer a period, softmax top-k routing with a gated shared
expert.

The ``qwen3_next`` language model of Qwen3-Next-80B-A3B (Qwen; its
``config.json``), as a train state on the same path as the other families
(``init_state`` / ``make_train_step`` / ``CheckpointManager``). The config
gives the widths, the counts, the layer period, the convolution's kernel,
the rotated share of a head and the routing; **the form of each mechanism is
written from memory of the papers and the release's modelling code, no
network** (Gated DeltaNet: arXiv:2412.06464; the gate on attention's
output and the zero-centred norms: the release's code as remembered), and
``benchmarks/chip/configs/qwen3next80b.json`` lists
every such form under ``assumed``. With ``rms0(x; w) = x rsqrt(mean(x^2) +
eps) (1 + w)`` (the scales held zero-centred, ``w`` starts at 0) in float32
on the stream ``x: (B, S, D)``, published layer ``p`` is full attention iff
``(p + 1) % full_attention_interval == 0``, else Gated DeltaNet (GDN), and
every layer has an expert MLP::

    x = E[tokens]
    per layer:  x <- x + mixer(rms0(x; g1));   x <- x + moe(rms0(x; g2))
    GDN   [q | k | v | z] = a W_qkvz,  [b | a'] = a W_ba       columns grouped by key head j: value heads r j .. r j + r - 1
          q, k, v = silu(conv4([q, k, v]))                       depthwise, causal, no bias
          q <- q / |q| / sqrt(d_k),  k <- k / |k| a key head;  each repeated over its r value heads
          beta = sigmoid(b);  g = -exp(A_log) softplus(a' + dt_bias)     one a value head, unbounded below
          o = gdn(q, k, v, g, beta)                                ops/gdn.py
          out = (rms_head(o; w_n) * silu(z)) W_o                 w_n as stored (starts at 1)
    attn  [q | gate] = a W_q a head;  k, v = a W_k, a W_v        fewer KV heads, each shared by a group
          q, k <- rope(rms0(. ; g_q / g_k)) a head, the LEADING rotary_dim of head_dim rotated
          out = (softmax(q k^T / sqrt(head_dim) + causal) v * sigmoid(gate)) W_o     the gate element by element
    moe   p = softmax(b W_r) (float32);  the top_k, weights p_i / sum of the chosen p
          out = sum over the chosen experts HELD HERE of w_i E_i(b) + sigmoid(b w_s) E_shared(b)
          E(b) = (silu(b G) * (b U)) D
    logits = rms0(x; g_f) W_head^T (untied);  loss = mean next-token cross-entropy

**The chip's share of the experts** is ``sdar30b``'s: the layer is told the
ids it holds (``cfg.held``), scores and chooses over all ``n_experts`` and
adds only its own experts' terms (``ops/moe.py`` ``softmax_topk_routed``;
no token is dropped); the shared expert and its gate are every chip's
alike. **The vocabulary held here** is a slice: ids are drawn from it,
logits and loss are over it. **What is not here** (the file's
``departures``): the multi-token prediction module, an auxiliary balancing
loss, generation and the caches decoding would keep.

How it is compiled: the layers of one kind are **stacked** in one subtree,
``gdn`` and ``attn``, and run in the published order: a run of consecutive
layers of one kind is a ``lax.scan`` over its slice of the stack, a run of
one layer a call, each layer a ``jax.checkpoint``. The GDN mixer runs
``GDN_HEAD_BLOCK`` value heads at a time, each block under its own
``jax.checkpoint``, and a GDN layer's checkpoint keeps the blocks' output
(``gdn_heads``), as ``kda_mla_lm.py`` does for KDA. Head and loss run in
blocks of ``head_block`` positions under ``jax.checkpoint``. The matrices
are cast to the compute dtype once a step and the train step differentiates
that tree; float32 stay the stream, every norm, the convolution, ``g``,
``beta``, the cumulative decays, the delta rule's algebra and state, the
rotation, the router end to end, and every matmul result.

Sharding: the batch over 'data', ``embed`` and ``head`` over the vocabulary
on 'model', the layers' leaves replicated (no multi-chip cell runs this
family).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..ops import gdn as gdn_ops
from ..ops.moe import held_tile_stats, sigmoid_gated_ffn, softmax_topk_routed
from . import kit
from .kit import causal_conv, constrainer, make_optimizer, mm, rmsnorm  # noqa: F401  (make_optimizer: the same optimizer)

Params = Dict[str, Any]
L2_EPS = 1e-6  # added under the squared L2 norm of a head's q and k (the release's l2norm, assumed)


@dataclasses.dataclass(frozen=True)
class GDNLMConfig:
    """Published sizes (defaults: Qwen3-Next-80B-A3B's ``config.json``).
    ``layers`` are the published ids of the layers held here, in order;
    ``held`` the experts whose weights live here."""

    vocab_size: int = 151936
    d_model: int = 2048
    layers: Tuple[int, ...] = tuple(range(48))
    full_attention_interval: int = 4
    n_key_heads: int = 16  # GDN
    n_value_heads: int = 32
    key_head_dim: int = 128
    value_head_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 64
    n_heads: int = 16  # gated attention
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64  # partial_rotary_factor 0.25 of head_dim
    rope_theta: float = 1e7
    n_experts: int = 512
    top_k: int = 10
    expert_ff: int = 512
    shared_ff: int = 512
    held: Tuple[int, ...] = tuple(range(512))
    norm_eps: float = 1e-6
    head_block: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(set(self.held)) != len(self.held) or not all(0 <= e < self.n_experts for e in self.held):
            raise ValueError(f"held expert ids {self.held} are not distinct ids below {self.n_experts}")
        if list(self.layers) != sorted(set(self.layers)) or not self.layers:
            raise ValueError(f"layer ids {self.layers} are not distinct and in order")
        if self.n_value_heads % self.n_key_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_value_heads} value heads over {self.n_key_heads} key heads, "
                             f"{self.n_heads} query heads over {self.n_kv_heads} KV heads")

    @property
    def kinds(self) -> List[str]:
        """Each held layer's stack: ``attn`` or ``gdn``."""
        return ["attn" if (p + 1) % self.full_attention_interval == 0 else "gdn" for p in self.layers]

    @property
    def runs(self) -> List[Tuple[str, int, int]]:
        """Consecutive held layers of one kind: ``(stack, first, end)``,
        ``first`` and ``end`` indices into the stack."""
        out, seen = [], {}
        for kind, group in itertools.groupby(self.kinds):
            n = len(list(group))
            first = seen.get(kind, 0)
            out.append((kind, first, first + n))
            seen[kind] = first + n
        return out

    @property
    def stack_sizes(self) -> Dict[str, int]:
        return {k: self.kinds.count(k) for k in dict.fromkeys(self.kinds)}

    @property
    def group(self) -> int:
        """Value heads a GDN key head serves."""
        return self.n_value_heads // self.n_key_heads

    def layer_matmul_params(self, kind: str) -> int:
        """Parameters one position multiplies by in a layer of ``kind``:
        every projection, the router, the shared expert and its gate, and of
        the held experts their expected share under even routing (``top_k *
        len(held) / n_experts`` of one)."""
        D = self.d_model
        Kd, Vd = self.n_key_heads * self.key_head_dim, self.n_value_heads * self.value_head_dim
        mixer = {
            "gdn": D * (2 * Kd + 2 * Vd) + D * 2 * self.n_value_heads + Vd * D,
            "attn": D * self.n_heads * 2 * self.head_dim + 2 * D * self.n_kv_heads * self.head_dim
            + self.n_heads * self.head_dim * D,
        }[kind]
        routed = round(self.top_k * len(self.held) / self.n_experts * 3 * D * self.expert_ff)
        return mixer + D * self.n_experts + 3 * D * self.shared_ff + D + routed

    @property
    def matmul_params_per_token(self) -> int:
        return sum(self.layer_matmul_params(k) for k in self.kinds) + self.vocab_size * self.d_model


# The residual branches write small: every last matrix of a branch (GDN's
# and attention's W_o, the experts' and the shared expert's down) at 0.3 of
# fan_in^-0.5, times the embedding's D^-0.5, as kda_mla_lm's and
# cca_moe_lm's (PERF.md §6): the routers then see their own token more than
# what the layers before wrote, and stay level over seeds
# (tests/test_gdn_lm.py; tools/routing_probe_gdn.py over a run's steps).
_BRANCH_OUT_SCALE = 0.3


def _init_stack(key: jax.Array, kind: str, L: int, cfg: GDNLMConfig) -> Params:
    """One stack's leaves, ``(L, ...)`` each. Matrices normal with std
    ``fan_in^-0.5``; the zero-centred norm scales 0 and GDN's output norm 1
    (the release's); GDN's convolution ``U(-1/2, 1/2)`` (PyTorch's default
    for a depthwise kernel of 4), ``A_log = log U(0, 16)`` and ``dt_bias``
    1 a value head (the release's)."""
    c, dt = cfg, cfg.param_dtype
    D = c.d_model
    keys = iter(jax.random.split(key, 24))
    out = _BRANCH_OUT_SCALE * D**-0.5

    def norm(shape, fan_in, scale=1.0):
        return jax.random.normal(next(keys), (L, *shape), dt) * (scale * fan_in**-0.5)

    w = {"ln1_scale": jnp.zeros((L, D), dt), "ln2_scale": jnp.zeros((L, D), dt)}
    if kind == "gdn":
        Hv, Kd, Vd = c.n_value_heads, c.n_key_heads * c.key_head_dim, c.n_value_heads * c.value_head_dim
        rate = 16.0 - jax.random.uniform(next(keys), (L, Hv), jnp.float32, 0.0, 16.0)  # U(0, 16], never 0
        w.update(
            qkvz=norm((D, 2 * Kd + 2 * Vd), D), ba=norm((D, 2 * Hv), D), o=norm((Vd, D), Vd, out),
            conv=jax.random.uniform(next(keys), (L, c.conv_kernel, 2 * Kd + Vd), dt, -0.5, 0.5),
            A_log=jnp.log(rate).astype(dt), dt_bias=jnp.ones((L, Hv), dt),
            o_norm=jnp.ones((L, c.value_head_dim), dt),
        )
    else:
        H, Hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim
        w.update(
            q=norm((D, H * 2 * hd), D), k=norm((D, Hkv * hd), D), v=norm((D, Hkv * hd), D),
            o=norm((H * hd, D), H * hd, out), q_norm=jnp.zeros((L, hd), dt), k_norm=jnp.zeros((L, hd), dt),
        )
    n, F, Fs = len(c.held), c.expert_ff, c.shared_ff
    w.update(
        router=norm((D, c.n_experts), D),
        expert_gate=norm((n, D, F), D), expert_up=norm((n, D, F), D), expert_down=norm((n, F, D), F, out),
        shared_gate=norm((D, Fs), D), shared_up=norm((D, Fs), D), shared_down=norm((Fs, D), Fs, out),
        shared_expert_gate=norm((D, 1), D),
    )
    return w


def init_params(rng: jax.Array, cfg: GDNLMConfig) -> Params:
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
        "ln_f_scale": jnp.zeros((D,), cfg.param_dtype),
    }


def param_specs(cfg: GDNLMConfig) -> Params:
    """PartitionSpecs on a ('data','model') mesh: ``embed`` and ``head`` over
    the vocabulary, every layer leaf replicated (module docstring)."""
    return kit.vocab_sharded(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))


# Leaves that feed a matmul in the compute dtype. The router's product is
# float32 at full precision and the convolution is elementwise: they stay.
_MATRICES = {"qkvz", "ba", "q", "k", "v", "o", "expert_gate", "expert_up", "expert_down", "shared_gate",
             "shared_up", "shared_down", "shared_expert_gate"}


def compute_params(params: Params, cfg: GDNLMConfig) -> Params:
    """The tree the layers read: every matrix that feeds a matmul (and the
    head) in the compute dtype, cast once a step; the embedding, the
    scales, the convolution, the per-head scalars and the router as stored."""
    return kit.cast_matrices(params, cfg.dtype, _MATRICES)


def rms0(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with a zero-centred scale, ``x rsqrt(mean(x^2) + eps) (1 +
    w)``, float32."""
    return rmsnorm(x, 1.0 + w.astype(jnp.float32), eps)


def _unit(x: jax.Array) -> jax.Array:
    """``x rsqrt(|x|^2 + eps)`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


# Value heads of a pass of the GDN mixer (a whole number of key heads' worth).
# At the cell's B 1 x S 8192 the step plans 13.28 GB of a chip's 15.75 with 8
# (16 at a time: 14.65; all 32 at once: 16.10, refused; 4: 12.24; PERF.md §4).
GDN_HEAD_BLOCK = 8
# What a GDN layer's checkpoint keeps for the backward pass: the head
# blocks' output alone. Module-level so that tests can swap it.
GDN_LAYER_POLICY = jax.checkpoint_policies.save_only_these_names("gdn_heads")


def head_block(cfg: GDNLMConfig) -> int:
    """Value heads a pass of the GDN mixer runs: ``GDN_HEAD_BLOCK``, or all of
    them where there are no more."""
    return min(GDN_HEAD_BLOCK, cfg.n_value_heads)


def _gdn_blocks(w: Params, cfg: GDNLMConfig) -> Params:
    """The GDN leaves cut by head, ``(blocks, ...)`` each: the key heads'
    columns of ``qkvz`` and ``ba`` with their value heads', the
    convolution's q, k and v channels, ``A_log`` and ``dt_bias``."""
    c = cfg
    nb = c.n_value_heads // head_block(c)
    Kd = c.n_key_heads * c.key_head_dim
    cut = lambda t: jnp.moveaxis(t.reshape(*t.shape[:-1], nb, -1), -2, 0)  # noqa: E731  (.., heads x) -> (nb, .., x)
    return {
        "qkvz": cut(w["qkvz"]), "ba": cut(w["ba"]), "A_log": cut(w["A_log"]), "dt_bias": cut(w["dt_bias"]),
        "q_conv": cut(w["conv"][:, :Kd]), "k_conv": cut(w["conv"][:, Kd:2 * Kd]), "v_conv": cut(w["conv"][:, 2 * Kd:]),
    }


def gdn_mixer(w: Params, a: jax.Array, cfg: GDNLMConfig) -> jax.Array:
    """The GDN mixer on the normed stream ``a: (B, S, D)`` -> float32.
    Everything ahead of ``W_o`` is a key head's own (with its value heads),
    so it runs ``head_block`` value heads at a time, each block under
    ``jax.checkpoint``. Their output, in the compute dtype ``W_o`` reads, is
    named ``gdn_heads``: the layer's checkpoint keeps it."""
    c = cfg
    B, S, _ = a.shape
    a = a.astype(c.dtype)
    o = jax.lax.map(jax.checkpoint(lambda wb: _gdn_heads({**wb, "o_norm": w["o_norm"]}, a, c)), _gdn_blocks(w, c))
    Vd = c.n_value_heads * c.value_head_dim
    o = checkpoint_name(jnp.moveaxis(o, 0, 2).reshape(B, S, Vd).astype(c.dtype), "gdn_heads")
    with jax.named_scope("gdn_proj"):
        return mm(o, w["o"])


def _gdn_heads(w: Params, a: jax.Array, cfg: GDNLMConfig) -> jax.Array:
    """The heads ``w``'s columns hold, up to ``W_o``: ``(B, S, h d_v)`` float32."""
    c, f32 = cfg, jnp.float32
    B, S, _ = a.shape
    r, dk, dv = c.group, c.key_head_dim, c.value_head_dim
    h = w["A_log"].shape[-1]  # value heads
    with jax.named_scope("gdn_proj"):
        mixed = mm(a, w["qkvz"]).reshape(B, S, h // r, -1)
        q, k, v, z = jnp.split(mixed, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        ba = mm(a, w["ba"]).reshape(B, S, h // r, 2, r)  # b then a', r of each a key head
        beta = jax.nn.sigmoid(ba[..., 0, :].reshape(B, S, h))
        a_ = ba[..., 1, :].reshape(B, S, h)
        g = -jnp.exp(w["A_log"].astype(f32)) * jax.nn.softplus(a_ + w["dt_bias"].astype(f32))
    with jax.named_scope("gdn_conv"):
        flat = lambda t: t.reshape(B, S, -1)  # noqa: E731
        q, k, v = (jax.nn.silu(causal_conv(flat(t), w[f"{n}_conv"], jnp.zeros((), f32)))
                   for t, n in ((q, "q"), (k, "k"), (v, "v")))
        q = jnp.repeat(_unit(q.reshape(B, S, h // r, dk)) * dk**-0.5, r, axis=2)
        k = jnp.repeat(_unit(k.reshape(B, S, h // r, dk)), r, axis=2)
    o = gdn_ops.gdn(q, k, v.reshape(B, S, h, dv), g, beta, chunk=c.chunk)
    with jax.named_scope("gdn_norm"):
        return rmsnorm(o, w["o_norm"], c.norm_eps).reshape(B, S, h * dv) * jax.nn.silu(z.reshape(B, S, h * dv))


def _rope_lead(x: jax.Array, rot: int, theta: float) -> jax.Array:
    """Rotary positions 0 .. S-1 on the leading ``rot`` of ``(B, S, H, d)``,
    rotate-half pairs ``(i, i + rot / 2)``; float32."""
    S = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2, keep = jnp.split(x, [rot // 2, rot], axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, keep], axis=-1)


def gattn_mixer(w: Params, a: jax.Array, cfg: GDNLMConfig, attend: Callable) -> jax.Array:
    """The gated attention mixer -> float32: ``attend`` repeats each KV head
    over its group of query heads (``ops/attention.py``)."""
    c = cfg
    B, S, _ = a.shape
    H, Hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim
    with jax.named_scope("gattn_proj"):
        q, gate = jnp.split(mm(a, w["q"]).reshape(B, S, H, 2 * hd), 2, axis=-1)
        k, v = mm(a, w["k"]).reshape(B, S, Hkv, hd), mm(a, w["v"]).reshape(B, S, Hkv, hd)
        q = _rope_lead(rms0(q, w["q_norm"], c.norm_eps), c.rotary_dim, c.rope_theta)
        k = _rope_lead(rms0(k, w["k_norm"], c.norm_eps), c.rotary_dim, c.rope_theta)
    with jax.named_scope("gattn_attn"):
        out = attend(q.astype(c.dtype), k.astype(c.dtype), v.astype(c.dtype))
    with jax.named_scope("gattn_gate"):
        out = out.astype(jnp.float32) * jax.nn.sigmoid(gate)
        return mm(out.reshape(B, S, H * hd), w["o"])


def moe_mlp(w: Params, b: jax.Array, cfg: GDNLMConfig):
    """The routed MLP and the gated shared expert -> (float32 output, the
    chosen expert ids (T, k))."""
    routed, ids = softmax_topk_routed(w, b, top_k=cfg.top_k, held=cfg.held)
    with jax.named_scope("moe_shared"):
        shared = sigmoid_gated_ffn(b, w["shared_gate"], w["shared_up"], w["shared_down"], w["shared_expert_gate"])
        return routed + shared, ids


def layer(w: Params, x: jax.Array, kind: str, cfg: GDNLMConfig, attend: Callable):
    """One layer on the float32 stream -> (the new stream, the chosen ids ``(T, k)``)."""
    c = cfg
    a = rms0(x, w["ln1_scale"], c.norm_eps)
    x = x + (gdn_mixer(w, a, c) if kind == "gdn" else gattn_mixer(w, a, c, attend))
    y, ids = moe_mlp(w, rms0(x, w["ln2_scale"], c.norm_eps), c)
    return x + y, ids


def select_attention(cfg: GDNLMConfig, mesh: Optional[Mesh], B: int, S: int) -> str:
    """The name of the attention route the full-attention layers run for this mesh and shape."""
    return kit.route_name(cfg.n_heads, mesh, B, S)


def _run_layers(cparams: Params, tokens: jax.Array, cfg: GDNLMConfig, mesh: Optional[Mesh]):
    """The closed hidden state ``rms0(x; g_f)`` ``(B, S, D)`` in float32 and,
    per layer in order, the chosen ids. ``cparams`` is ``compute_params``'
    tree."""
    B, S = tokens.shape
    cs = constrainer(mesh)
    stream = P("data", None, None)
    _, attend = kit.attention_route(cfg.n_heads, mesh, B, S)
    x = cs(cparams["embed"][tokens].astype(jnp.float32), stream)
    chosen = []
    for kind, first, end in cfg.runs:

        @functools.partial(jax.checkpoint, policy=GDN_LAYER_POLICY if kind == "gdn" else None)
        def one(x, w, kind=kind):
            x, ids = layer(w, x, kind, cfg, attend)
            return cs(x, stream), ids

        stack = cparams["layers"][kind]
        if end - first == 1:
            x, ids = one(x, jax.tree_util.tree_map(lambda t: t[first], stack))
            ids = ids[None]
        else:
            x, ids = jax.lax.scan(one, x, jax.tree_util.tree_map(lambda t: t[first:end], stack))
        chosen += list(ids)
    return rms0(x, cparams["ln_f_scale"], cfg.norm_eps), chosen


def forward(params: Params, tokens: jax.Array, cfg: GDNLMConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    """(B, S) int32 tokens -> (B, S, vocab) float32 logits."""
    p = compute_params(params, cfg)
    return kit.head(_run_layers(p, tokens, cfg, mesh)[0], p["head"], constrainer(mesh))


def chosen_experts(params: Params, tokens: jax.Array, cfg: GDNLMConfig) -> List[jax.Array]:
    """Per layer in order, the ids ``(T, top_k)`` each position chose, from
    the forward pass the train step runs."""
    return _run_layers(compute_params(params, cfg), tokens, cfg, None)[1]


def routing_stats(params: Params, tokens: jax.Array, cfg: GDNLMConfig) -> Dict[str, jax.Array]:
    """What the routers did with this batch, ``(L,)`` a statistic:
    ``held_share``, the share of the ``top_k * T`` assignments that fall on
    experts held here (``len(held) / n_experts`` under even routing);
    ``max_over_mean``, the most positions a held expert gets over their
    mean; ``held_counts`` ``(L, n)``; and the loops' ``trips`` and
    ``tile_fill`` (``ops/moe.py`` ``held_tile_stats``)."""
    ids = jnp.stack(chosen_experts(params, tokens, cfg))  # (L, T, k)
    held = jnp.asarray(cfg.held, jnp.int32)
    counts = jnp.sum(ids[:, None] == held[None, :, None, None], axis=(2, 3))  # (L, n)
    return {
        "held_counts": counts,
        "held_share": jnp.sum(counts, axis=1) / (ids.shape[1] * ids.shape[2]),
        "max_over_mean": jnp.max(counts, axis=1) / jnp.maximum(jnp.mean(counts.astype(jnp.float32), axis=1), 1e-9),
        **held_tile_stats(counts, ids.shape[1]),
    }


def _objective(cparams: Params, batch: Dict[str, jax.Array], cfg: GDNLMConfig, mesh: Optional[Mesh]):
    h, _ = _run_layers(cparams, batch["tokens"], cfg, mesh)
    cs = constrainer(mesh)
    return kit.blocked_cross_entropy(h, cparams["head"], batch["targets"], cfg.head_block, lambda h, w: kit.head(h, w, cs))


def loss_fn(
    params: Params, batch: Dict[str, jax.Array], cfg: GDNLMConfig, *, mesh: Optional[Mesh] = None
) -> jax.Array:
    """Mean next-token cross-entropy over the vocabulary held here. No
    auxiliary loss: the config gives no coefficient."""
    return _objective(compute_params(params, cfg), batch, cfg, mesh)


def state_specs(cfg: GDNLMConfig, state: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_state``'s output (``kit.state_specs``)."""
    return kit.state_specs(param_specs(cfg), state)


def init_state(
    rng: jax.Array, cfg: GDNLMConfig, tx: optax.GradientTransformation, *, mesh: Optional[Mesh] = None
) -> Dict[str, Any]:
    """{params, opt_state, step}, the whole of it placed per ``state_specs``
    under a mesh."""
    return kit.init_state(init_params(rng, cfg), param_specs(cfg), tx, mesh)


def make_train_step(
    cfg: GDNLMConfig, tx: optax.GradientTransformation, *, mesh: Optional[Mesh] = None
) -> Callable:
    """Returns train_step(state, batch) -> (state, loss), ready to jit
    (``kit.make_train_step``)."""
    gauges = {"gdn_lm.layers": len(cfg.layers), "gdn_lm.matmul_params_per_token": cfg.matmul_params_per_token}

    def objective_args(state, batch):
        # Set as the step is traced, where the batch's shape is first known:
        # the form the delta rule runs (its index in ops/gdn.py ROUTES), the
        # value heads a pass of the GDN mixer, and the experts held.
        c = cfg
        route = gdn_ops.gdn_route(batch["tokens"].shape[1], c.key_head_dim, c.value_head_dim, c.chunk)
        telemetry.gauge_set("gdn_lm.gdn_route", gdn_ops.ROUTES.index(route))
        telemetry.gauge_set("gdn_lm.gdn_head_block", head_block(c))
        telemetry.gauge_set("gdn_lm.held_experts", len(c.held))
        return batch, cfg, mesh

    return kit.make_train_step(
        _objective, lambda p: compute_params(p, cfg), objective_args, tx, mesh, param_specs(cfg), gauges,
    )
