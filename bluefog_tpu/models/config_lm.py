"""A decoder-only LM whose block is read from a configuration.

Where :class:`~bluefog_tpu.models.TransformerLM` fixes its block in code, this one takes an
:class:`LMConfig` -- the keys of a published ``config.json``, of the DeepSeek-V3 kind (latent
attention, a biased sigmoid router, a shared expert, MTP), of the grouped-query kind
(SmallThinker: k/v heads shared by a group of query heads, layers that differ in mask and rotary,
a router that reads the block's input; Trinity: q/k norms, a gate on attention's output, sandwich
norms around the expert layer) or of the looped kind (Ouro: one stack of layers run
``total_ut_steps`` times on the same weights) -- and builds, layer by layer:

  attention  ``"latent"``: multi-head latent attention (two low-rank paths with an RMSNorm on
             each latent, a no-rope part per head and one rope part shared by all heads, a q.k
             width of ``qk_nope + qk_rope`` and a narrower v); ``"equal"``: equal-width heads
             from one fused projection; ``"grouped"``: separate q, k and v projections,
             ``num_attention_heads`` query heads of ``head_dim`` over ``num_key_value_heads``
             k/v heads (query head h reads k/v head h // group; the attention function is
             handed k and v at their own head count), and with ``qk_norm`` an RMSNorm over
             ``head_dim`` on q and on k before rotary. Rope turns pairs ``(2i, 2i+1)``
             (``rope_interleave``) or halves. By layer: ``rope_layout[i]`` 0 leaves layer i
             without rotary (NoPE), and ``sliding_window_layout[i]`` 1 gives it a causal window
             of ``sliding_window`` tokens (the attention function's ``window``) where the others
             see the whole past. ``attn_output_gate``: the attention's output times
             ``sigmoid(h W_gate)``, elementwise, before the output projection (h the normed
             input; the sigmoid and the product in float32, rounded once).
  FFN        a SwiGLU of ``intermediate_size`` in the ``first_k_dense_replace`` leading layers
             and wherever there are no experts; otherwise
             :class:`~bluefog_tpu.parallel.expert.RoutedExperts`: top-k of ``n_routed_experts``
             by sigmoid or softmax score, plus (unless ``routing_bias`` is off) a choice-only
             bias kept in the ``"routing"`` collection and moved by the auxiliary-loss-free
             balancing rule, not by the optimizer; the experts ``experts_held`` computed here,
             gated by ``expert_act`` (SiLU or ReLU); ``n_shared_experts`` shared experts or
             none. The router reads the FFN's normed input (``router_input="ffn"``) or the
             attention's (``"block"``: its scores are made before attention).
  MTP        ``num_nextn_predict_layers`` multi-token-prediction modules after the last layer,
             sharing the embedding and the head.

and around the layers:

  embedding  ``embedding_scale``: a lookup (the trunk's input, an MTP module's next token) is
             the embedding's output times it.
  the loop   ``total_ut_steps`` R > 1 runs the stack R times over the same ``layer_i``
             submodules (the parameter tree has L layers, not R x L: a weight's gradient is the
             sum over its R uses). After every pass the one ``final_norm`` is applied and its
             output is what the next pass reads; the one head reads it too.
  sandwich   ``sandwich_norms``: a second norm on what attention returns (``attn_out_norm``)
             and on what the FFN returns, dense or the expert layer's routed and shared sum
             (``ffn_out_norm``), each before its residual add: four norms a layer.
  exit gate  ``exit_gate``: one ``Linear(hidden -> 1)`` with a bias, shared by the passes,
             gives a float32 logit a token and pass from the normed state;
             :func:`exit_distribution` turns the R logits into the probability of leaving
             after each pass and :func:`looped_exit_loss` is the expected-exit objective.
  recompute  ``remat_layers``: every layer application runs under ``jax.checkpoint``
             (``nn.remat`` of :class:`Layer`) and again in the backward pass, from its
             input. An attention function that takes ``name_residuals`` (the flash kernel)
             is asked to name its residuals (q, k, v, output, row statistics), which the
             policy keeps too: its forward kernel does not run again. Memory is state for L
             layers beside R x L inputs and residuals, not R x L applications' activations.

Every norm is an RMSNorm with a learned scale, no bias but the exit gate's, sequential residuals.
Parameters are float32; ``dtype`` is the compute type; router scores, the top-k and the exit gate
are float32 whatever it is.

The parts run under ``jax.named_scope``s a trace reducer can find them by: ``bf.mla.proj``
(latent and equal-width attention outside its kernels: projections, latent norms and rope) or
``bf.attn.proj`` (the same of the grouped kind, its q/k norms too), ``bf.attn.gate`` (the output
gate's product and sigmoid, beside ``bf.attn.proj`` and not in it), the two ``bf.flash.*`` of the
attention function, ``bf.moe.route`` / ``bf.moe.experts`` / ``bf.moe.shared``, ``bf.ffn.dense``,
``bf.lm.head`` (the final norm, the exit gate, the head and its loss), ``bf.mtp`` around a whole
MTP module and, in a looped or exit-gated model only, ``bf.loop.<t>`` (t from 0) around
everything pass t runs, outside the others. Gauges set while tracing (docs/metrics.md): ``loop.*``,
``attn.gated_layers``, ``attn.qk_normed_layers``; by the two losses, ``loss.compare_heads``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..parallel.context import reference_attention
from ..parallel.expert import ROUTING, SCOPE_ROUTE, RoutedExperts, SwiGLU
from ..runtime import metrics

SCOPE_MLA_PROJ = "bf.mla.proj"
SCOPE_ATTN_PROJ = "bf.attn.proj"   # the grouped kind's, under a name of its own
SCOPE_ATTN_GATE = "bf.attn.gate"   # the output gate, beside bf.attn.proj
SCOPE_DENSE_FFN = "bf.ffn.dense"
SCOPE_HEAD = "bf.lm.head"
SCOPE_MTP = "bf.mtp"
SCOPE_LOOP = "bf.loop."            # + the pass, from 0: looped models only


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The block, under the names a DeepSeek-V3 style ``config.json`` gives it
    (and, for what that style lacks, a grouped-query one's)."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    intermediate_size: int
    attention: str = "latent"            # "latent" | "equal" | "grouped"
    num_key_value_heads: int = 0         # "grouped": k/v heads (0: as many as q heads)
    head_dim: int = 0                    # "grouped": a head's width
    sliding_window: int = 0              # tokens a window layer sees
    sliding_window_layout: Optional[Tuple[int, ...]] = None  # by layer, 1: window; None: none
    rope_layout: Optional[Tuple[int, ...]] = None            # by layer, 0: no rotary; None: all
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6
    first_k_dense_replace: int = 0
    n_routed_experts: int = 0            # the router's width; 0: every layer is dense
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None  # ids computed here; None: all
    expert_act: str = "silu"             # the experts' gate: "silu" | "relu"
    routing_bias: bool = True            # False: no bias, the top-k is of the scores
    router_input: str = "ffn"            # "ffn" | "block": the attention's normed input
    bias_update_speed: float = 0.0       # the balancing rule's step; 0: the bias stays
    num_nextn_predict_layers: int = 0
    total_ut_steps: int = 1              # passes over the one stack of layers
    sandwich_norms: bool = False         # a norm on attention's and the FFN's output too
    exit_gate: bool = False              # Linear(hidden -> 1) + bias on every pass's state
    remat_layers: bool = False           # jax.checkpoint around every layer application
    qk_norm: bool = False                # "grouped": an RMSNorm over head_dim on q and on k
    attn_output_gate: bool = False       # attention's output times sigmoid(h W_gate)
    embedding_scale: float = 1.0         # an embedding lookup is its output times it

    def __post_init__(self):
        # the loop and the exit gate are the dense block's: an expert layer sows one set of
        # counters and keeps one routing bias a step, and an MTP module reads the un-normed trunk
        if (self.total_ut_steps > 1 or self.exit_gate) and (
                self.n_routed_experts or self.num_nextn_predict_layers):
            raise ValueError("a looped stack (total_ut_steps > 1, exit_gate) with expert layers "
                             "or MTP modules is not supported")
        if self.qk_norm and self.attention != "grouped":
            raise ValueError(f"qk_norm is the grouped attention's, not {self.attention!r}")

    @classmethod
    def from_dict(cls, doc: dict, **overrides) -> "LMConfig":
        """From the keys of a ``config.json`` (others are ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        picked = {**{k: v for k, v in doc.items() if k in names}, **overrides}
        return cls(**{k: tuple(v) if isinstance(v, list) else v for k, v in picked.items()})

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    def is_expert_layer(self, layer: int) -> bool:
        return self.n_routed_experts > 0 and layer >= self.first_k_dense_replace

    def window_of(self, layer: int) -> Optional[int]:
        """The sliding window of a layer of the trunk, None where it sees the whole past."""
        layout = self.sliding_window_layout
        return self.sliding_window if layout and layout[layer] else None

    def rotary_in(self, layer: int) -> bool:
        return self.rope_layout is None or bool(self.rope_layout[layer])

    @property
    def proj_scope(self) -> str:
        """The scope of attention outside its kernels: the grouped kind's is its own."""
        return SCOPE_ATTN_PROJ if self.attention == "grouped" else SCOPE_MLA_PROJ


def rope(x, positions, theta: float, interleave: bool):
    """Rotary embedding of ``x [B, S, H, D]`` at ``positions [S]`` or ``[B, S]``:
    pairs ``(2i, 2i+1)`` if ``interleave``, else ``(i, i + D/2)``."""
    d2 = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    if positions.ndim == 1:
        positions = positions[None]
    angle = positions[..., None].astype(jnp.float32) * freqs       # [B, S, d2]
    sin, cos = jnp.sin(angle)[:, :, None, :], jnp.cos(angle)[:, :, None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        pairs = xf.reshape(xf.shape[:-1] + (d2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = xf[..., :d2], xf[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


class Attention(nn.Module):
    """The attention residual's inner part: normed input in, ``[B, S, d]`` out."""

    cfg: LMConfig
    dtype: Any
    attn_fn: Callable
    rotary: bool = True

    @nn.compact
    def __call__(self, h, positions):
        cfg = self.cfg
        heads, d = cfg.num_attention_heads, h.shape[-1]
        dense = partial(nn.Dense, dtype=self.dtype, param_dtype=jnp.float32, use_bias=False)
        norm = partial(nn.RMSNorm, epsilon=cfg.rms_norm_eps, dtype=self.dtype,
                       param_dtype=jnp.float32)
        turn = partial(rope, positions=positions, theta=cfg.rope_theta,
                       interleave=cfg.rope_interleave) if self.rotary else (lambda x: x)
        lead = h.shape[:2]
        with jax.named_scope(cfg.proj_scope):
            if cfg.attention == "grouped":
                kv_heads = cfg.num_key_value_heads or heads
                q = dense(heads * cfg.head_dim, name="q")(h).reshape(lead + (heads, -1))
                k = dense(kv_heads * cfg.head_dim, name="k")(h).reshape(lead + (kv_heads, -1))
                v = dense(kv_heads * cfg.head_dim, name="v")(h).reshape(lead + (kv_heads, -1))
                if cfg.qk_norm:
                    q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
                q, k = turn(q), turn(k)
            elif cfg.attention == "equal":
                q, k, v = jnp.split(dense(3 * d, name="qkv")(h), 3, axis=-1)
                q, k, v = (t.reshape(lead + (heads, d // heads)) for t in (q, k, v))
                q, k = turn(q), turn(k)
            else:
                nope, rot, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
                c_q = norm(name="q_a_norm")(dense(cfg.q_lora_rank, name="q_a")(h))
                q = dense(heads * (nope + rot), name="q_b")(c_q).reshape(
                    lead + (heads, nope + rot))
                kv_a = dense(cfg.kv_lora_rank + rot, name="kv_a")(h)
                c_kv = norm(name="kv_a_norm")(kv_a[..., :cfg.kv_lora_rank])
                k_rot = turn(kv_a[..., None, cfg.kv_lora_rank:])       # one head, shared
                kv = dense(heads * (nope + dv), name="kv_b")(c_kv).reshape(
                    lead + (heads, nope + dv))
                q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
                k = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(k_rot, lead + (heads, rot))], axis=-1)
                v = kv[..., nope:]
        a = self.attn_fn(q, k, v)
        if cfg.attn_output_gate:
            a = _output_gate(a.reshape(lead + (-1,)), h, dense)
        with jax.named_scope(cfg.proj_scope):
            return dense(d, name="o")(a.reshape(lead + (-1,)))


class Layer(nn.Module):
    """One pre-norm block: attention, then a dense SwiGLU or the expert layer, each part's output
    normed too before its residual add with ``sandwich_norms``; ``attn_fn`` is the layer's own."""

    cfg: LMConfig
    experts: bool
    dtype: Any
    attn_fn: Callable
    interpret: bool = False
    rotary: bool = True

    @nn.compact
    def __call__(self, x, positions, choice=None):
        cfg = self.cfg
        norm = partial(nn.RMSNorm, epsilon=cfg.rms_norm_eps, dtype=self.dtype,
                       param_dtype=jnp.float32)
        with jax.named_scope(cfg.proj_scope):
            h = norm(name="attn_norm")(x)
        router_logits = None
        if self.experts and cfg.router_input == "block":
            router = self.param("router", nn.initializers.lecun_normal(),
                                (x.shape[-1], cfg.n_routed_experts), jnp.float32)
            with jax.named_scope(SCOPE_ROUTE):
                router_logits = jnp.dot(h.astype(jnp.float32), router,
                                        precision=jax.lax.Precision.HIGHEST)
        a = Attention(cfg, self.dtype, self.attn_fn, self.rotary, name="attn")(h, positions)
        if cfg.sandwich_norms:
            with jax.named_scope(cfg.proj_scope):
                a = norm(name="attn_out_norm")(a)
        x = x + a
        if self.experts:
            h = norm(name="ffn_norm")(x)
            combined = RoutedExperts(
                num_experts=cfg.n_routed_experts, experts_per_token=cfg.num_experts_per_tok,
                d_ff=cfg.moe_intermediate_size, held=cfg.held, n_shared=cfg.n_shared_experts,
                scoring=cfg.scoring_func, scaling=cfg.routed_scaling_factor,
                bias_update_speed=cfg.bias_update_speed, dtype=self.dtype, interpret=self.interpret,
                activation=cfg.expert_act, routing_bias=cfg.routing_bias, name="ffn")(
                    h, choice, router_logits)
            return x + (norm(name="ffn_out_norm")(combined) if cfg.sandwich_norms else combined)
        with jax.named_scope(SCOPE_DENSE_FFN):
            h = norm(name="ffn_norm")(x)
            m = SwiGLU(cfg.intermediate_size, self.dtype, name="ffn")(h)
            return x + (norm(name="ffn_out_norm")(m) if cfg.sandwich_norms else m)


class ConfigLM(nn.Module):
    """Causal LM built from an :class:`LMConfig`.

    ``model.apply({"params": p}, tokens)`` gives the logits ``[B, S, V]`` in float32; with MTP
    modules it gives ``(logits, mtp_logits)``, where ``mtp_logits[k][:, i]`` predicts token
    ``i + k + 2`` from the trunk's output at ``i`` and the embeddings of tokens ``i + 1 .. i + k
    + 1`` (``next_tokens [B, S]`` is token ``i + 1`` at position ``i``; by default the sequence
    rolled by one, whose last position wraps).

    ``attn_fn(q, k, v) -> out`` defaults to dense causal attention; ``partial(flash_attention,
    causal=True)`` is the kernel path. A layer with a sliding window calls it with
    ``window=<size>`` bound, and the grouped kind hands it k and v at ``num_key_value_heads``.
    ``choices`` (one ``[B, S, k]`` array of expert ids per expert layer, forward order) forces
    the experts each token takes. Every expert layer sows its counters and its choice
    (``mutable=["intermediates"]``; :func:`moe_counters`) and keeps its routing bias in the
    ``"routing"`` collection, which ``init`` returns beside ``"params"`` and ``apply`` takes too.

    A looped model (``total_ut_steps`` R > 1) gives the last pass's logits; with
    ``all_passes=True`` it gives ``(states [R, B, S, d], gate_logits [R, B, S])`` instead --
    every pass's normed state and, with an exit gate, its float32 logit (else ``None``) -- and
    leaves the head to the caller (:meth:`head`), so that one pass's ``[T, V]`` logits at a
    time need be alive (:func:`looped_exit_loss`).
    """

    cfg: LMConfig
    dtype: Any = jnp.float32
    attn_fn: Optional[Callable] = None
    interpret: bool = False  # Pallas interpreter for the grouped products (CPU tests)

    def setup(self):
        # setattr gives every submodule its name (flax takes none in setup)
        cfg = self.cfg
        attn = self.attn_fn or partial(reference_attention, causal=True)
        norm = partial(nn.RMSNorm, epsilon=cfg.rms_norm_eps, dtype=self.dtype,
                       param_dtype=jnp.float32)
        # recomputed, an application keeps its input and what ``attn`` names
        attn, layer = _recomputed(attn) if cfg.remat_layers else (attn, Layer)
        layer = partial(layer, cfg, dtype=self.dtype, attn_fn=attn, interpret=self.interpret)
        blocks = cfg.num_hidden_layers + cfg.num_nextn_predict_layers   # gauges set while tracing
        metrics.gauge("attn.gated_layers").set(blocks * cfg.attn_output_gate)
        metrics.gauge("attn.qk_normed_layers").set(blocks * cfg.qk_norm)
        self.embed = _embedding(cfg)(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
                                     param_dtype=jnp.float32)
        for i in range(cfg.num_hidden_layers):
            own = {} if cfg.window_of(i) is None else {
                "attn_fn": partial(attn, window=cfg.window_of(i))}
            setattr(self, f"layer_{i}", layer(experts=cfg.is_expert_layer(i),
                                              rotary=cfg.rotary_in(i), **own))
        self.final_norm = norm()
        self.lm_head = nn.Dense(cfg.vocab_size, dtype=self.dtype, param_dtype=jnp.float32,
                                use_bias=False)
        if cfg.exit_gate:
            self.exit_gate = nn.Dense(1, dtype=jnp.float32, param_dtype=jnp.float32)
        # an MTP module: norms of the trunk's output and of the next token's
        # embedding, a 2d -> d projection, one block, its own final norm
        for k in range(cfg.num_nextn_predict_layers):
            setattr(self, f"mtp_{k}_h_norm", norm())
            setattr(self, f"mtp_{k}_e_norm", norm())
            setattr(self, f"mtp_{k}_proj", nn.Dense(
                cfg.hidden_size, dtype=self.dtype, param_dtype=jnp.float32, use_bias=False))
            setattr(self, f"mtp_{k}_block", layer(experts=cfg.n_routed_experts > 0))
            setattr(self, f"mtp_{k}_final_norm", norm())

    def _head(self, x, final_norm):
        with jax.named_scope(SCOPE_HEAD):
            return self.lm_head(final_norm(x)).astype(jnp.float32)

    def head(self, state):
        """The logits ``[..., V]`` in float32 of a normed state ``[..., d]``
        (a pass's, from ``all_passes=True``)."""
        with jax.named_scope(SCOPE_HEAD):
            return self.lm_head(state).astype(jnp.float32)

    def __call__(self, tokens, positions=None, next_tokens=None,
                 choices: Optional[Sequence] = None, all_passes: bool = False):
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        choices = iter(choices) if choices is not None else None
        take = lambda block: next(choices) if block.experts and choices is not None else None
        passes = cfg.total_ut_steps
        metrics.gauge("loop.passes").set(passes)   # trace-time gauges (docs/metrics.md)
        metrics.gauge("loop.layer_applications").set(passes * cfg.num_hidden_layers)
        metrics.gauge("loop.recomputed").set(int(cfg.remat_layers))
        metrics.gauge("loop.kept_residual_bytes").set(_kept_residual_bytes(self, tokens.shape))

        def stack(x):
            for i in range(cfg.num_hidden_layers):
                block = getattr(self, f"layer_{i}")
                x = block(x, positions, take(block))
            return x

        x = self.embed(tokens)
        if passes == 1 and not (cfg.exit_gate or all_passes):
            # a plain model's path as it was: flax puts ``_head`` on its ops' paths
            x = stack(x)
            logits = self._head(x, self.final_norm)
        else:
            states, gates = [], []
            for t in range(passes):
                with jax.named_scope(f"{SCOPE_LOOP}{t}"):
                    x = stack(x)
                    with jax.named_scope(SCOPE_HEAD):
                        x = self.final_norm(x)      # the normed state is what is fed on
                        if cfg.exit_gate:
                            gates.append(self.exit_gate(x.astype(jnp.float32))[..., 0])
                    states.append(x)
            if all_passes:
                return jnp.stack(states), jnp.stack(gates) if gates else None
            logits = self.head(x)
        if not cfg.num_nextn_predict_layers:
            return logits
        if next_tokens is None:
            next_tokens = jnp.roll(tokens, -1, axis=1)
        mtp_logits = []
        for k in range(cfg.num_nextn_predict_layers):
            part = lambda name: getattr(self, f"mtp_{k}_{name}")
            with jax.named_scope(SCOPE_MTP):
                ahead = self.embed(jnp.roll(next_tokens, -k, axis=1))
                x = part("proj")(jnp.concatenate(
                    [part("h_norm")(x), part("e_norm")(ahead)], axis=-1))
                x = part("block")(x, positions, take(part("block")))
                mtp_logits.append(self._head(x, part("final_norm")))
        return logits, tuple(mtp_logits)


def _sowed(intermediates, name: str) -> list:
    """What every expert layer sowed under ``name``, in forward order (the
    trunk's layers by index, then the MTP modules')."""
    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            intermediates, is_leaf=lambda x: isinstance(x, tuple))[0]:
        keys = [getattr(k, "key", None) for k in path]
        if keys[-1] == name:
            found[keys[0]] = leaf[0]
    order = lambda module: (module.startswith("mtp_"), int(module.split("_")[1]))
    return [found[module] for module in sorted(found, key=order)]


def moe_counters(intermediates) -> dict:
    """The expert layers' counters of one forward pass, from the collection a
    ``mutable=["intermediates"]`` apply returns: ``rows_routed``,
    ``rows_overflowed``, ``tiles_in_use`` and ``gather_trips`` summed over
    the layers, ``load_max_over_mean`` the largest of them. Empty for a model
    without expert layers."""
    layers = _sowed(intermediates, "moe_counters")
    if not layers:
        return {}
    summed = ("rows_routed", "rows_overflowed",
              "tiles_in_use", "gather_trips")
    return {**{key: sum(c[key] for c in layers) for key in summed},
            "load_max_over_mean": jnp.max(jnp.stack(
                [c["load_max_over_mean"] for c in layers]))}


def moe_choices(intermediates) -> list:
    """Every expert layer's chosen ids ``[B, S, k]``, in forward order: what
    ``ConfigLM``'s ``choices`` takes."""
    return _sowed(intermediates, "moe_choice")


@jax.custom_vjp
def label_cross_entropy(logits, labels):
    """The cross-entropy ``[...]`` in float32 of integer ``labels`` ``[...]``
    under ``logits`` ``[..., V]``: ``logsumexp(z) - z[label]``, the label's
    logit picked by comparing a vocabulary iota with the label inside the
    reduce that reads ``z`` -- no gather from the ``[tokens, V]`` array, no
    reshape of it.

    The gradient is its own: from the logits as they came, the ``[...]``
    log-sum-exp and the labels, ``(exp(z - lse) - (iota == label)) * g`` in
    float32, rounded once to the logits' dtype -- one elementwise pass that
    reads the logits and writes their gradient, where the transpose of a
    gather scatters into a zeroed float32 ``[tokens, V]`` array."""
    return _label_cross_entropy_fwd(logits, labels)[0]


def _is_label(shape, labels):
    return jax.lax.broadcasted_iota(labels.dtype, shape, len(shape) - 1) == labels[..., None]


def _label_cross_entropy_fwd(logits, labels):
    z = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.sum(jnp.where(_is_label(z.shape, labels), z, 0.0), axis=-1)
    return lse - picked, (logits, lse, labels)


def _label_cross_entropy_bwd(saved, g):
    logits, lse, labels = saved
    softmax = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    d_logits = (softmax - _is_label(logits.shape, labels)) * g[..., None]
    return d_logits.astype(logits.dtype), None


label_cross_entropy.defvjp(_label_cross_entropy_fwd, _label_cross_entropy_bwd)


def next_token_loss(model: ConfigLM, mtp_weight: float = 0.3):
    """``loss_fn(params, routing, batch) -> (loss, (routing, counters))`` for
    the ``bf.Distributed*Optimizer``s with ``with_model_state=True``: the mean
    cross-entropy of the next token, plus ``mtp_weight`` times that of each
    MTP module's. ``routing`` is the model's ``"routing"`` collection (the
    expert layers' biases; ``{}`` without expert layers) and goes to
    ``opt.init(params, model_state=routing)``; it comes back moved by the
    balancing rule. ``batch`` is ``(tokens, targets)`` or, with MTP,
    ``(tokens, targets, mtp_targets)``: ``targets`` are the tokens one on
    (they are also what the first MTP module embeds), ``mtp_targets`` two on.
    ``opt.step``'s ``metrics["aux"]`` carries :func:`moe_counters`. Every
    head's cross-entropy is :func:`label_cross_entropy`; the gauge
    ``loss.compare_heads`` counts them while tracing."""

    def loss_fn(params, routing, batch):
        tokens, targets = batch[0], batch[1]
        out, state = model.apply({"params": params, ROUTING: routing}, tokens,
                                 next_tokens=targets, mutable=["intermediates", ROUTING])
        if not model.cfg.num_nextn_predict_layers:
            loss = label_cross_entropy(out, targets).mean()
        else:
            logits, mtp_logits = out
            with jax.named_scope(SCOPE_HEAD):
                loss = label_cross_entropy(logits, targets).mean()
            with jax.named_scope(SCOPE_MTP):
                for k, extra in enumerate(mtp_logits):
                    loss = loss + mtp_weight * label_cross_entropy(
                        extra, jnp.roll(batch[2], -k, axis=1)).mean()
        metrics.gauge("loss.compare_heads").set(1 + model.cfg.num_nextn_predict_layers)
        return loss, (state.get(ROUTING, routing),
                      moe_counters(state.get("intermediates", {})))

    return loss_fn


def exit_distribution(gate_logits):
    """The probability of leaving after each pass, ``[R, ...]`` in float32 and
    its logarithm, from the gate's logits ``[R, ...]``: with lambda_t =
    sigmoid(logit_t), ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < R
    and ``p_R = prod_{j<R} (1 - lambda_j)`` -- whoever has not left by the
    last pass leaves there, so the R of them sum to 1 and the last pass's own
    logit plays no part."""
    g = gate_logits.astype(jnp.float32)
    before = jnp.concatenate([                                   # log prod_{j<t} (1 - lambda_j)
        jnp.zeros_like(g[:1]), jnp.cumsum(jax.nn.log_sigmoid(-g[:-1]), axis=0)])
    log_p = jnp.concatenate([before[:-1] + jax.nn.log_sigmoid(g[:-1]), before[-1:]])
    return jnp.exp(log_p), log_p


def looped_exit_loss(model: ConfigLM, beta: float):
    """``loss_fn(params, state, batch) -> (loss, (state, aux))`` for the
    ``bf.Distributed*Optimizer``s with ``with_model_state=True`` (the state
    is empty: ``opt.init(params, model_state={})``): the expected-exit
    objective of a looped model with an exit gate, in float32 -- the mean over
    tokens of ``sum_t p_t CE(head(state_t), target) - beta H(p)``, p the
    :func:`exit_distribution` of the token's R gate logits and H its entropy.

    The head and its cross-entropy run once a pass, each under
    ``jax.checkpoint``: a pass keeps its ``[B, S]`` losses and the backward
    pass makes its ``[T, V]`` logits again, so no more than one pass's logits
    and their gradient are alive at a time. ``batch`` is ``(tokens,
    targets)``. ``opt.step``'s ``metrics["aux"]`` carries ``loss_by_pass``
    ``[R]`` (each pass's mean cross-entropy), ``exit_mass_by_pass`` ``[R]``
    (the mean of p, summing to 1), ``exit_entropy`` (the mean of H) and
    ``expected_exit_pass`` (the mean of ``sum_t t p_t``, passes counted from
    1). A pass's cross-entropy is :func:`label_cross_entropy`; the gauge
    ``loss.compare_heads`` counts the passes' while tracing."""

    @jax.checkpoint
    def pass_loss(head, state, targets):
        return label_cross_entropy(
            model.apply({"params": {"lm_head": head}}, state, method=ConfigLM.head), targets)

    def loss_fn(params, model_state, batch):
        tokens, targets = batch[0], batch[1]
        states, gate_logits = model.apply({"params": params}, tokens, all_passes=True)
        by_pass = []
        for t in range(states.shape[0]):
            with jax.named_scope(f"{SCOPE_LOOP}{t}"), jax.named_scope(SCOPE_HEAD):
                by_pass.append(pass_loss(params["lm_head"], states[t], targets))
        metrics.gauge("loss.compare_heads").set(len(by_pass))
        with jax.named_scope(SCOPE_HEAD):
            by_pass = jnp.stack(by_pass)                          # [R, B, S]
            p, log_p = exit_distribution(gate_logits)
            entropy = -jnp.sum(p * log_p, axis=0)
            loss = jnp.mean(jnp.sum(p * by_pass, axis=0) - beta * entropy)
            number = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
            aux = {"loss_by_pass": by_pass.mean(axis=(1, 2)),
                   "exit_mass_by_pass": p.mean(axis=(1, 2)),
                   "exit_entropy": entropy.mean(),
                   "expected_exit_pass": jnp.tensordot(number, p, 1).mean()}
        return loss, (model_state, aux)

    return loss_fn


def _names_residuals(attn_fn) -> bool:
    """Whether ``attn_fn`` takes ``name_residuals`` (``flash_attention`` and
    partials of it do): seen through its signature, not its identity."""
    # this and the next function import here: a line added above ``Attention``
    # would move the source positions that the kernels' serialized bodies hold
    from inspect import signature

    return attn_fn is not None and "name_residuals" in signature(attn_fn).parameters


def _recomputed(attn_fn):
    """(attention function, layer class) of a recomputed stack: ``nn.remat``
    of :class:`Layer` under a policy that keeps the attention kernel's named
    residuals, with ``attn_fn`` asked to name them, where it can; else the
    application's input is all it keeps."""
    from ..parallel.flash import RESIDUAL_NAMES

    if not _names_residuals(attn_fn):
        return attn_fn, nn.remat(Layer)
    policy = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
    return partial(attn_fn, name_residuals=True), nn.remat(Layer, policy=policy)


def _kept_residual_bytes(model: ConfigLM, tokens_shape) -> int:
    """Bytes a forward pass of ``model`` over ``tokens [B, S]`` keeps of its
    attention kernels for the backward (the gauge ``loop.kept_residual_bytes``):
    an application's q, k, v and output in the compute type and its float32 row
    max and sum, times the applications; 0 where nothing is recomputed or the
    attention function names nothing."""
    cfg = model.cfg
    if not (cfg.remat_layers and _names_residuals(model.attn_fn)):
        return 0
    heads = cfg.num_attention_heads
    if cfg.attention == "latent":   # k a head each, its rope part broadcast
        kv_heads, d_qk, d_v = heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
    else:
        width = cfg.head_dim if cfg.attention == "grouped" else cfg.hidden_size // heads
        kv_heads, d_qk, d_v = (cfg.num_key_value_heads or heads), width, width
    words = (heads + kv_heads) * d_qk + (kv_heads + heads) * d_v       # q, k | v, out
    applications = cfg.total_ut_steps * cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    return applications * tokens_shape[0] * tokens_shape[1] * (
        words * jnp.dtype(model.dtype).itemsize + 2 * 4 * heads)


def _output_gate(a, h, dense):
    """``a * sigmoid(h W_gate)`` of attention's output ``a [..., Hq * Dv]`` and its normed input
    ``h`` under ``bf.attn.gate``: ``W_gate`` the parameter ``gate`` ``[d, Hq * Dv]``, no bias,
    made by ``dense`` (the caller's ``nn.Dense`` in the compute type); the sigmoid and the
    product in float32, rounded once to ``a``'s dtype."""
    with jax.named_scope(SCOPE_ATTN_GATE):
        g = dense(a.shape[-1], name="gate")(h).astype(jnp.float32)
        return (a.astype(jnp.float32) * jax.nn.sigmoid(g)).astype(a.dtype)


class _ScaledEmbed(nn.Embed):
    """``nn.Embed`` whose lookups are its output times ``scale``, in the compute type."""

    scale: float = 1.0

    def __call__(self, inputs):
        return super().__call__(inputs) * self.scale


def _embedding(cfg: LMConfig):
    """The embedding's module: ``nn.Embed`` itself at ``embedding_scale`` 1, where the traced
    program holds no product."""
    if cfg.embedding_scale == 1.0:
        return nn.Embed
    return partial(_ScaledEmbed, scale=cfg.embedding_scale)
