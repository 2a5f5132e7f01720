"""Plain reference of a causal decoder with WINDOW and FULL attention
layers mixed, gated attention, and a sigmoid-routed mixture of experts
beside a shared expert, as the published Trinity-Mini ``config.json``
(``model_type`` ``afmoe``) describes it and, where the config has no key,
as the configuration's ``assumed`` lists it.

The model: ``x0 = Embed[ids] * sqrt(hidden_size)`` (``mup_enabled``);
the decoder layers; ``logits = RMSNorm(x_L) . W_head`` (untied).  For a
layer with input ``x [T, hidden]``:

1. ``h = RMSNorm(x)``; ``q = h.Wq`` (heads x head_dim), ``k = h.Wk``,
   ``v = h.Wv`` (kv heads x head_dim), ``g = h.Wg`` (the width of q);
2. ``q <- RMSNorm(q)``, ``k <- RMSNorm(k)`` over the ``head_dim`` lanes of
   every head, one learned weight each, before any rotation;
3. where ``layer_types[l]`` is ``sliding_attention``: rotate-half RoPE
   on q and k, and query ``i`` sees key ``j`` iff ``0 <= i - j <
   sliding_window``.  Where it is ``full_attention``: NO rotation and
   the plain causal mask.  The window is a MASK here: every key is
   there, none is ever dropped;
4. ``a = softmax(q.k^T / sqrt(head_dim) + M).v`` (grouped queries);
   ``a <- a * sigmoid(g)``; ``x1 = x + RMSNorm(a.Wo)``;
5. ``h2 = RMSNorm(x1)``.  In the first ``num_dense_layers`` layers
   ``m = (silu(h2.Wgate) * (h2.Wup)).Wdown``.  In the others
   ``s = sigmoid(h2.Wr)`` over all experts; the ``num_experts_per_tok``
   largest of ``s + b`` (``b`` the layer's ``expert_bias``, used for the
   SELECTION only); ``w = s[chosen]``; ``w <- w / (sum w + 1e-20)``
   (``route_norm``); ``w <- route_scale . w``; ``m = sum_e w_e .
   Expert_e(h2) + Shared(h2)``, every expert and the shared one a
   SiLU-gated MLP.  No capacity, no dropped token;
6. ``x2 = x1 + RMSNorm(m)``: four norms a layer.

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: one full forward over a whole
token row, no cache, no kernel, no batching; a token's experts are
gathered and applied one token at a time.  Weights are widened from the
type they are served in as they are used.  So that a row of 2.6 k tokens
fits beside the served model at the published widths, attention is
computed a block of ``ROWS`` query rows at a time and the head a block of
``VOCAB_BLOCK`` columns at a time; the arithmetic is the same.

With ``witness=`` (the experts the served path chose, ``[routed layers,
T, k]`` int32) ``logits`` REPLAYS those choices and VERIFIES each against
its own arithmetic, and returns ``(want, report)``.

``MARGIN``: a chosen expert's SELECTION SCORE ``sigmoid(logit) + bias``
may lie this far under the reference's k-th largest: ``2^-5``, eight bf16
epsilons of a sigmoid's range (0..1).  The served path forms its router
logits from a residual stream rounded to bf16 at every layer boundary;
the allowance is the one ``LOGIT_TOL`` gives an output logit for the same
reason, and the tests' own routed toy (a normalised-sigmoid router too)
is held to the same.  Measured: PERF.md section 4.

The weights are the program's own arrays, read by the parameter and
buffer names of ``paddle_tpu.models.AfmoeForCausalLM`` (projections
``[in, out]``; expert matrices ``[experts, expert_width, hidden]`` for
gate, up and the transposed down).  A layer's kind is part of the
STRUCTURE of what ``weights_of`` returns (the key its attention weights
lie under, and ``"dense"`` or ``"routed"``), so the layers keep their
kinds whatever a caller does to the list.  ``causal_lm_loss`` is absent
on purpose: the configuration is served only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MARGIN = 2.0 ** -5
ROWS = 512              # query rows of one attention block
VOCAB_BLOCK = 32768     # head columns of one block
SLIDING, FULL = "sliding_attention", "full_attention"


def weights_of(model) -> dict:
    """``{"embed", "norm", "head", "layers": [...]}`` of the arrays
    ``model`` holds now (no copy).  A layer is ``{"norms", kind:
    attention weights, "dense" or "routed": feed-forward weights}`` with
    ``kind`` the layer's entry of ``layer_types``."""
    named = {n: p._value for n, p in model.named_parameters()}
    named.update({n: b._value for n, b in model.named_buffers()})
    n_layers = 1 + max(int(n.split(".")[2]) for n in named
                       if n.startswith("model.layers."))
    layers = []
    for i in range(n_layers):
        at = f"model.layers.{i}."

        def mlp(prefix):
            return {k: named[f"{at}{prefix}.{k}_proj.weight"]
                    for k in ("gate", "up", "down")}

        layer = {
            "norms": {k: named[f"{at}{k}.weight"] for k in (
                "input_layernorm", "post_attention_layernorm",
                "pre_mlp_layernorm", "post_mlp_layernorm")},
            model.config.layer_types[i]: {
                **{k: named[f"{at}self_attn.{k}_proj.weight"]
                   for k in ("q", "k", "v", "gate", "o")},
                "q_norm": named[f"{at}self_attn.q_norm.weight"],
                "k_norm": named[f"{at}self_attn.k_norm.weight"]}}
        if f"{at}mlp.router" in named:
            layer["routed"] = {
                **{k: named[f"{at}mlp.{k}"] for k in (
                    "router", "w_gate", "w_up", "w_down", "expert_bias")},
                "shared": mlp("shared_expert")}
        else:
            layer["dense"] = mlp("mlp")
        layers.append(layer)
    return {"embed": named["model.embed_tokens.weight"],
            "norm": named["model.norm.weight"],
            "head": named["lm_head.weight"], "layers": layers}


def _f32(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: [T, H, D], positions 0..T-1, rotate-half."""
    t, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv_freq)
    c, s = jnp.cos(freqs)[:, None, :], jnp.sin(freqs)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _gated_mlp(h, w):
    w = _f32(w)
    return (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta", "window"))
def _attention(x, norms, w, *, heads, kv_heads, head_dim, eps, theta,
               window):
    """Steps 1-4 over ``x [T, hidden]``; ``window`` None is a full
    layer (no rotation, causal mask)."""
    w, norms = _f32(w), _f32(norms)
    t = x.shape[0]
    h = _rms_norm(x, norms["input_layernorm"], eps)
    q = _rms_norm((h @ w["q"]).reshape(t, heads, head_dim), w["q_norm"], eps)
    k = _rms_norm((h @ w["k"]).reshape(t, kv_heads, head_dim), w["k_norm"],
                  eps)
    v = (h @ w["v"]).reshape(t, kv_heads, head_dim)
    g = h @ w["gate"]
    if window is not None:
        q, k = _rope(q, theta), _rope(k, theta)
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
        out.append(jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(scores, axis=-1), v))
    a = jnp.concatenate(out).reshape(t, heads * head_dim)
    a = a * jax.nn.sigmoid(g)
    return x + _rms_norm(a @ w["o"], norms["post_attention_layernorm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, norms, w, *, eps):
    norms = _f32(norms)
    h = _rms_norm(x, norms["pre_mlp_layernorm"], eps)
    return x + _rms_norm(_gated_mlp(h, w), norms["post_mlp_layernorm"], eps)


@functools.partial(jax.jit, static_argnames=(
    "eps", "k", "sigmoid", "normalize", "scale"))
def _routed(x, norms, w, chosen, *, eps, k, sigmoid, normalize, scale):
    """Steps 5-6 of a routed layer.  ``chosen [T, k]`` are the experts
    to apply, ``None`` for the reference's own top-k.  Also returns, for
    every choice, how far its selection score lies below the k-th
    largest, and whether it is outside the reference's own top-k."""
    norms = _f32(norms)
    h = _rms_norm(x, norms["pre_mlp_layernorm"], eps)
    z = h @ w["router"].astype(jnp.float32)
    s = jax.nn.sigmoid(z) if sigmoid else jax.nn.softmax(z, axis=-1)
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

    def one_token(args):
        h_t, chosen_t, gates_t = args
        gate = jnp.einsum("kmh,h->km",
                          w["w_gate"][chosen_t].astype(jnp.float32), h_t)
        up = jnp.einsum("kmh,h->km",
                        w["w_up"][chosen_t].astype(jnp.float32), h_t)
        return jnp.einsum("km,kmh->h",
                          jax.nn.silu(gate) * up * gates_t[:, None],
                          w["w_down"][chosen_t].astype(jnp.float32))

    m = jax.lax.map(one_token, (h, chosen, gates)) \
        + _gated_mlp(h, w["shared"])
    return (x + _rms_norm(m, norms["post_mlp_layernorm"], eps),
            shortfall, not_first)


@functools.partial(jax.jit, static_argnames=("eps", "last"))
def _final(x, norm, *, eps, last):
    return _rms_norm(x[-last:], norm.astype(jnp.float32), eps)


def _row(weights, cfg, tokens, last, chosen):
    """``(logits [last, V], shortfalls, not-first flags)`` of one row;
    ``chosen`` is ``[routed layers, T, k]`` or ``None``."""
    shortfalls, not_first, routed = [], [], 0
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        if cfg.get("mup_enabled", True):
            x = x * math.sqrt(cfg["hidden_size"])
        for layer in weights["layers"]:
            kind = SLIDING if SLIDING in layer else FULL
            x = _attention(
                x, layer["norms"], layer[kind],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], eps=eps,
                theta=float(cfg["rope_theta"]),
                window=cfg["sliding_window"] if kind == SLIDING else None)
            if "dense" in layer:
                x = _dense(x, layer["norms"], layer["dense"], eps=eps)
                continue
            x, s, n = _routed(
                x, layer["norms"], layer["routed"],
                None if chosen is None else chosen[routed], eps=eps,
                k=cfg["num_experts_per_tok"],
                sigmoid=cfg.get("score_func", "sigmoid") == "sigmoid",
                normalize=bool(cfg.get("route_norm", True)),
                scale=float(cfg.get("route_scale", 1.0)))
            routed += 1
            shortfalls.append(s)
            not_first.append(n)
        x = _final(x, weights["norm"], eps=eps, last=last)
        head = weights["head"]
        logits = jnp.concatenate([
            x @ head[:, lo:lo + VOCAB_BLOCK].astype(jnp.float32)
            for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=1)
        return logits, shortfalls, not_first


def _admissible(witness, cfg, layers, length):
    """The witness as ``[routed layers, T, k]`` int32 where it names
    ``k`` different experts that exist at every position, else
    ``None``."""
    w = np.asarray(witness)
    k, experts = cfg["num_experts_per_tok"], cfg["num_experts"]
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
    routed = sum(1 for layer in weights["layers"] if "routed" in layer)
    w = _admissible(witness, cfg, routed, len(tokens))
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
