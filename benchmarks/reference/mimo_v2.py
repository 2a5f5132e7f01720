"""Plain reference of a causal decoder with WINDOW and FULL attention
layers of different head counts, keys wider than their values, a sink
logit in the window layers' softmax, and a sigmoid-routed mixture of
experts of which a replica holds a share, as the published MiMo-V2.5
``config.json`` (``model_type`` ``mimo_v2``) describes it and, where the
config has no key, as the configuration's ``assumed`` lists it.

The model: ``x0 = Embed[ids]``; the decoder layers; ``logits =
RMSNorm(x_L) . W_head`` (untied).  For a layer with input ``x [T,
hidden]``, of kind ``hybrid_layer_pattern[l]`` (0 full, 1 window):

1. ``h = RMSNorm(x)``; ``q = h.Wq`` (heads x d), ``k = h.Wk`` (kv heads
   x d), ``v = h.Wv`` (kv heads x dv), where a full layer has
   ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
   ``v_head_dim`` and a window layer the ``swa_`` keys' own;
2. rotate-half RoPE on the first ``r = 2 floor(d . partial_rotary_factor
   / 2)`` lanes of every q and k head (pairs ``i, i + r/2``, inverse
   frequencies ``theta^(-2i/r)``; ``theta`` is ``rope_theta`` on a full
   layer, ``swa_rope_theta`` on a window layer); the other lanes as
   they are;
3. ``s_j = q.k_j / sqrt(d)`` over the keys the layer sees: ``j <= i``
   on a full layer, ``0 <= i - j < sliding_window`` on a window layer.
   The window is a MASK here: every key is there, none is ever dropped;
4. where the layer has a sink (``add_swa_attention_sink_bias`` on a
   window layer, ``add_full_attention_sink_bias`` on a full one), the
   head's sink logit ``sigma_h`` joins the softmax as one more column
   that is then dropped: ``p_j = exp(s_j) / (exp(sigma_h) + sum_k
   exp(s_k))``; else the plain softmax;
5. ``o = attention_value_scale . sum_j p_j v_j`` (grouped queries);
   ``x1 = x + o.Wo``;
6. ``h2 = RMSNorm(x1)``.  Where ``moe_layer_freq[l]`` is 0, ``m =
   (silu(h2.Wgate) * (h2.Wup)).Wdown``.  Elsewhere ``s = sigmoid(h2.Wr)``
   over ALL the router's experts; the ``num_experts_per_tok`` largest of
   ``s + b`` (``b`` the layer's ``e_score_correction_bias``, used for the
   SELECTION only); ``w = s[chosen] / (sum s[chosen] + 1e-20)``
   (``norm_topk_prob``) times ``routed_scaling_factor`` (1 where null);
   ``m = sum over chosen experts this replica HOLDS of w_e .
   Expert_e(h2)``, each a SiLU-gated MLP: no capacity, no dropped
   token, no shared expert, and nothing for the experts other replicas
   hold;
7. ``x2 = x1 + m``.

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: one full forward over a whole
token row, no cache, no kernel, no batching.  Weights are widened from
the type they are served in as they are used.  Departures, none in the
arithmetic: attention is computed a block of ``ROWS`` query rows at a
time and the head a block of ``VOCAB_BLOCK`` columns at a time, and a
held expert runs over every token of the row with the weight of the
tokens that did not choose it 0, so that a row of 2.75 k tokens fits
beside the served model at the published widths.

With ``witness=`` (the experts the served path chose, ``[routed layers,
T, k]`` int32 ids among the router's experts) ``logits`` REPLAYS those
choices and VERIFIES each against its own arithmetic, and returns
``(want, report)``.

``MARGIN``: a chosen expert's SELECTION SCORE ``sigmoid(logit) + bias``
may lie this far under the reference's k-th largest: ``2^-5``, eight bf16
epsilons of a sigmoid's range (0..1), Trinity's margin for the same form
of router (``benchmarks/reference/afmoe.py``).

The weights are the program's own arrays, read by the parameter and
buffer names of ``paddle_tpu.models.MimoV2ForCausalLM`` (projections
``[in, out]``; expert matrices ``[held experts, expert_width, hidden]``
for gate, up and the transposed down).  A layer's kind is part of the
STRUCTURE of what ``weights_of`` returns (the key its attention weights
lie under, and ``"dense"`` or ``"routed"``), as is which experts the
replica holds, so the layers keep their kinds whatever a caller does to
the list.  ``causal_lm_loss`` is absent on purpose: the configuration is
served only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MARGIN = 2.0 ** -5
ROWS = 256              # query rows of one attention block
VOCAB_BLOCK = 32768     # head columns of one block
KINDS = {0: "full", 1: "window"}


def weights_of(model) -> dict:
    """``{"embed", "norm", "head", "layers": [...]}`` of the arrays
    ``model`` holds now (no copy).  A layer is ``{"norms", kind:
    attention weights, "dense" or "routed": feed-forward weights}`` with
    ``kind`` ``"full"`` or ``"window"``; a routed layer's ``"held"`` are
    the router's ids of its expert matrices, in order."""
    named = {n: p._value for n, p in model.named_parameters()}
    named.update({n: b._value for n, b in model.named_buffers()})
    cfg = model.config
    layers = []
    for i in range(cfg.num_hidden_layers):
        at = f"model.layers.{i}."
        attention = {k: named[f"{at}self_attn.{k}_proj.weight"]
                     for k in ("q", "k", "v", "o")}
        if f"{at}self_attn.attention_sink_bias" in named:
            attention["sink"] = named[f"{at}self_attn.attention_sink_bias"]
        layer = {
            "norms": {k: named[f"{at}{k}.weight"] for k in (
                "input_layernorm", "post_attention_layernorm")},
            KINDS[cfg.hybrid_layer_pattern[i]]: attention}
        if f"{at}mlp.router" in named:
            n_held = named[f"{at}mlp.w_gate"].shape[0]
            layer["routed"] = {
                **{k: named[f"{at}mlp.{k}"] for k in (
                    "router", "w_gate", "w_up", "w_down", "expert_bias")},
                "held": jnp.asarray(range(n_held) if cfg.held_experts is None
                                    else cfg.held_experts, jnp.int32)}
        else:
            layer["dense"] = {k: named[f"{at}mlp.{k}_proj.weight"]
                              for k in ("gate", "up", "down")}
        layers.append(layer)
    return {"embed": named["model.embed_tokens.weight"],
            "norm": named["model.norm.weight"],
            "head": named["lm_head.weight"], "layers": layers}


def _f32(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rotary_lanes(head_dim, factor) -> int:
    return 2 * int(head_dim * factor / 2)


def _rope(x, theta, rotary):
    """x: [T, H, D], positions 0..T-1, rotate-half on the first
    ``rotary`` lanes."""
    t = x.shape[0]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                                / rotary))
    freqs = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv_freq)
    c, s = jnp.cos(freqs)[:, None, :], jnp.sin(freqs)[:, None, :]
    x1, x2 = jnp.split(x[..., :rotary], 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s,
                            x[..., rotary:]], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "v_head_dim", "eps", "theta",
    "rotary", "window", "value_scale"))
def _attention(x, norms, w, *, heads, kv_heads, head_dim, v_head_dim, eps,
               theta, rotary, window, value_scale):
    """Steps 1-5 over ``x [T, hidden]``; ``window`` None is a full
    layer; ``w["sink"]``, where there is one, the heads' sink logits."""
    w, norms = _f32(w), _f32(norms)
    t = x.shape[0]
    h = _rms_norm(x, norms["input_layernorm"], eps)
    q = _rope((h @ w["q"]).reshape(t, heads, head_dim), theta, rotary)
    k = _rope((h @ w["k"]).reshape(t, kv_heads, head_dim), theta, rotary)
    v = (h @ w["v"]).reshape(t, kv_heads, v_head_dim)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    j = jnp.arange(t)
    out = []
    for lo in range(0, t, ROWS):            # a block of query rows
        i = jnp.arange(lo, min(lo + ROWS, t))
        back = i[:, None] - j[None, :]
        see = back >= 0
        if window is not None:
            see = see & (back < window)
        scores = jnp.einsum("qhd,khd->hqk", q[lo:lo + ROWS], k) \
            / jnp.sqrt(jnp.float32(head_dim))
        scores = jnp.where(see[None], scores, -jnp.inf)
        if "sink" in w:
            # one more column a head, dropped after the softmax
            sink = jnp.broadcast_to(w["sink"][:, None, None],
                                    scores.shape[:2] + (1,))
            p = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1),
                               axis=-1)[..., :-1]
        else:
            p = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    a = value_scale * jnp.concatenate(out).reshape(t, heads * v_head_dim)
    return x + a @ w["o"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, norms, w, *, eps):
    norms, w = _f32(norms), _f32(w)
    h = _rms_norm(x, norms["post_attention_layernorm"], eps)
    return x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnames=("eps", "k", "normalize",
                                             "scale"))
def _routed(x, norms, w, chosen, *, eps, k, normalize, scale):
    """Steps 6-7 of a routed layer.  ``chosen [T, k]`` are the experts
    to apply, ``None`` for the reference's own top-k.  Also returns, for
    every choice, how far its selection score lies below the k-th
    largest, and whether it is outside the reference's own top-k."""
    norms = _f32(norms)
    h = _rms_norm(x, norms["post_attention_layernorm"], eps)
    s = jax.nn.sigmoid(h @ w["router"].astype(jnp.float32))
    select = s + w["expert_bias"].astype(jnp.float32)
    best, own = jax.lax.top_k(select, k)
    if chosen is None:
        chosen = own
    picked = jnp.take_along_axis(select, chosen, axis=-1)
    shortfall = jnp.maximum(best[:, -1:] - picked, 0.0)
    not_first = (chosen[:, :, None] != own[:, None, :]).all(-1)
    gates = jnp.take_along_axis(s, chosen, axis=-1)     # never the bias
    if normalize:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * scale
    m = jnp.zeros_like(x)
    for e in range(w["w_gate"].shape[0]):   # the experts held here
        weight = jnp.sum(jnp.where(chosen == w["held"][e], gates, 0.0),
                         axis=-1)
        g = h @ w["w_gate"][e].astype(jnp.float32).T
        u = h @ w["w_up"][e].astype(jnp.float32).T
        m = m + weight[:, None] * (
            (jax.nn.silu(g) * u) @ w["w_down"][e].astype(jnp.float32))
    return x + m, shortfall, not_first


@functools.partial(jax.jit, static_argnames=("eps", "last"))
def _final(x, norm, *, eps, last):
    return _rms_norm(x[-last:], norm.astype(jnp.float32), eps)


def _shape(cfg, kind):
    """A layer kind's attention settings from the configuration."""
    p = "swa_" if kind == "window" else ""
    d = cfg[p + "head_dim"]
    sink = cfg.get("add_swa_attention_sink_bias", True) if kind == "window" \
        else cfg.get("add_full_attention_sink_bias", False)
    return dict(heads=cfg[p + "num_attention_heads"],
                kv_heads=cfg[p + "num_key_value_heads"], head_dim=d,
                v_head_dim=cfg[p + "v_head_dim"],
                theta=float(cfg[p + "rope_theta"]),
                rotary=rotary_lanes(d, cfg["partial_rotary_factor"]),
                window=cfg["sliding_window"] if kind == "window" else None), \
        sink


def _row(weights, cfg, tokens, last, chosen):
    """``(logits [last, V], shortfalls, not-first flags)`` of one row;
    ``chosen`` is ``[routed layers, T, k]`` or ``None``."""
    shortfalls, not_first, routed = [], [], 0
    eps = cfg["layernorm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for layer in weights["layers"]:
            kind = "window" if "window" in layer else "full"
            shape, sink = _shape(cfg, kind)
            w = dict(layer[kind])
            if not sink:
                w.pop("sink", None)
            x = _attention(x, layer["norms"], w, eps=eps,
                           value_scale=float(cfg["attention_value_scale"]),
                           **shape)
            if "dense" in layer:
                x = _dense(x, layer["norms"], layer["dense"], eps=eps)
                continue
            x, s, n = _routed(
                x, layer["norms"], layer["routed"],
                None if chosen is None else chosen[routed], eps=eps,
                k=cfg["num_experts_per_tok"],
                normalize=bool(cfg.get("norm_topk_prob", True)),
                scale=float(cfg.get("routed_scaling_factor") or 1.0))
            routed += 1
            shortfalls.append(s)
            not_first.append(n)
        x = _final(x, weights["norm"], eps=eps, last=last)
        head = weights["head"]
        logits = jnp.concatenate([
            x @ head[:, lo:lo + VOCAB_BLOCK].astype(jnp.float32)
            for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=1)
        return logits, shortfalls, not_first


def _admissible(witness, k, experts, layers, length):
    """The witness as ``[routed layers, T, k]`` int32 where it names
    ``k`` different experts that exist at every position, else
    ``None``."""
    w = np.asarray(witness)
    if w.shape != (layers, length, k) or w.dtype.kind not in "iu":
        return None
    if w.min() < 0 or w.max() >= experts:
        return None
    ordered = np.sort(w, axis=-1)
    if (ordered[..., 1:] == ordered[..., :-1]).any():
        return None
    return w.astype(np.int32)


def logits(weights, cfg, tokens, last, witness=None):
    """float32 logits ``[last, V]`` of the final ``last`` positions of
    the 1-D row ``tokens``; with a witness ``(logits, report)``: ``ok``
    (every choice's selection score within ``MARGIN`` of the
    reference's k-th best), ``decisions``, ``not_first_choice``,
    ``largest_shortfall`` and ``margin`` (both in units of the selection
    score)."""
    if witness is None:
        return _row(weights, cfg, tokens, last, None)[0]
    routed = [layer["routed"] for layer in weights["layers"]
              if "routed" in layer]
    # the router's experts, whatever share of them the replica holds
    experts = routed[0]["router"].shape[1] if routed else 0
    w = _admissible(witness, cfg["num_experts_per_tok"], experts,
                    len(routed), len(tokens))
    if w is None:
        # (every number of a report is finite: it is printed as JSON)
        return _row(weights, cfg, tokens, last, None)[0], {
            "ok": False, "decisions": 0, "not_first_choice": 0,
            "largest_shortfall": 1e9, "margin": MARGIN}
    want, shortfalls, not_first = _row(weights, cfg, tokens, last,
                                       jnp.asarray(w))
    worst = max((float(s.max()) for s in shortfalls), default=0.0)
    return want, {
        "ok": worst <= MARGIN,
        "decisions": int(sum(s.size for s in shortfalls)),
        "not_first_choice": int(sum(int(n.sum()) for n in not_first)),
        "largest_shortfall": worst, "margin": MARGIN}


def causal_lm_loss(weights, cfg, batch, witness=None):
    raise NotImplementedError(
        "this configuration is served only: no training cell compares a "
        "loss with this reference")
