"""Plain reference of a causal decoder with multi-head LATENT attention
(a low-rank query, one compressed key/value and one shared rotary key a
position) and a sigmoid-routed mixture of experts beside a shared
expert, as the published GLM-4.7-Flash ``config.json`` (``model_type``
``glm4_moe_lite``) describes it and, where the config has no key, as the
configuration's ``assumed`` lists it.

The model: ``x0 = Embed[ids]``; the decoder layers; ``logits =
RMSNorm(x_L) . W_head`` (untied).  For a layer with input ``x [T,
hidden]``:

1. ``h = RMSNorm(x)``; ``c_q = RMSNorm(h.W_qa)`` (``q_lora_rank``);
   ``q = c_q.W_qb`` a head, split into ``q_nope`` (``qk_nope_head_dim``)
   and ``q_rope`` (``qk_rope_head_dim``);
2. ``[c_raw | k_r] = h.W_kva``; ``c_kv = RMSNorm(c_raw)``
   (``kv_lora_rank``); ``k_rope = RoPE(k_r)``: ONE rotary key a position,
   shared by every head; ``q_rope <- RoPE(q_rope)`` (rotate-half over all
   ``qk_rope_head_dim`` lanes, ``rope_theta``, no scaling);
3. the EXPANDED form, and only it: ``[k_nope_h | v_h] = c_kv.W_kvb`` a
   head (``qk_nope_head_dim + v_head_dim``), every position's keys and
   values written out; ``score_h(i, j) = (q_nope_h(i).k_nope_h(j) +
   q_rope_h(i).k_rope(j)) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``,
   causal; ``o_h = softmax_j(score).v_h``; ``x1 = x + concat_h(o).W_o``.
   (The served path computes the ABSORBED form, the query carried into
   the latent space and the value projection after the softmax: the same
   algebra in another order of roundings.  That the two orders check
   each other is the point of keeping this one plain.);
4. ``h2 = RMSNorm(x1)``.  In the first ``first_k_dense_replace`` layers
   ``m = (silu(h2.Wgate) * (h2.Wup)).Wdown``.  In the others ``s =
   sigmoid(h2.Wr)`` over all experts; the ``num_experts_per_tok`` largest
   of ``s + b`` (``b`` the layer's ``e_score_correction_bias``, used for
   the SELECTION only); ``w = s[chosen]``; ``w <- w / (sum w + 1e-20)``
   (``norm_topk_prob``); ``w <- routed_scaling_factor . w``; ``m = sum_e
   w_e . Expert_e(h2) + Shared(h2)``, every expert and the shared one a
   SiLU-gated MLP.  No capacity, no dropped token;
5. ``x2 = x1 + m``: two norms a layer, both on a branch's input.

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
reason, and the one ``reference/afmoe.py`` states for the same form of
router.  Measured: PERF.md section 6.

The weights are the program's own arrays, read by the parameter and
buffer names of ``paddle_tpu.models.Glm4MoeLiteForCausalLM`` (projections
``[in, out]``; expert matrices ``[experts, expert_width, hidden]`` for
gate, up and the transposed down; the selection bias under the routed
layer's ``expert_bias``).  ``causal_lm_loss`` is absent on purpose: the
configuration is served only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MARGIN = 2.0 ** -5
ROWS = 512              # query rows of one attention block
VOCAB_BLOCK = 32768     # head columns of one block


def weights_of(model) -> dict:
    """``{"embed", "norm", "head", "layers": [...]}`` of the arrays
    ``model`` holds now (no copy).  A layer is ``{"norms", "attn",
    "dense" or "routed": feed-forward weights}``."""
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
                "input_layernorm", "post_attention_layernorm")},
            "attn": {
                **{k: named[f"{at}self_attn.{k}.weight"] for k in (
                    "q_a_proj", "q_b_proj", "kv_a_proj_with_mqa",
                    "kv_b_proj", "o_proj", "q_a_layernorm",
                    "kv_a_layernorm")}}}
        if f"{at}mlp.router" in named:
            layer["routed"] = {
                **{k: named[f"{at}mlp.{k}"] for k in (
                    "router", "w_gate", "w_up", "w_down", "expert_bias")},
                "shared": mlp("shared_experts")}
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
    """x: [T, H, D], positions 0..T-1, rotate-half over all D lanes."""
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
    "heads", "rank", "nope", "rope", "v_dim", "eps", "theta"))
def _attention(x, norms, w, *, heads, rank, nope, rope, v_dim, eps, theta):
    """Steps 1-3 over ``x [T, hidden]``, every head's keys and values
    written out."""
    w, norms = _f32(w), _f32(norms)
    t = x.shape[0]
    h = _rms_norm(x, norms["input_layernorm"], eps)
    c_q = _rms_norm(h @ w["q_a_proj"], w["q_a_layernorm"], eps)
    q = (c_q @ w["q_b_proj"]).reshape(t, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
    kv = h @ w["kv_a_proj_with_mqa"]
    c_kv = _rms_norm(kv[:, :rank], w["kv_a_layernorm"], eps)
    k_rope = _rope(kv[:, None, rank:], theta)           # [T, 1, rope]
    up = (c_kv @ w["kv_b_proj"]).reshape(t, heads, nope + v_dim)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_rope, (t, heads, rope))], -1)
    v = up[..., nope:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    j = jnp.arange(t)
    out = []
    for lo in range(0, t, ROWS):            # a block of query rows
        i = jnp.arange(lo, min(lo + ROWS, t))
        scores = jnp.einsum("qhd,khd->hqk", q[lo:lo + ROWS], k) \
            / jnp.sqrt(jnp.float32(nope + rope))
        scores = jnp.where((i[:, None] >= j[None, :])[None], scores,
                           -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(scores, axis=-1), v))
    a = jnp.concatenate(out).reshape(t, heads * v_dim)
    return x + a @ w["o_proj"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, norms, w, *, eps):
    h = _rms_norm(x, norms["post_attention_layernorm"].astype(jnp.float32),
                  eps)
    return x + _gated_mlp(h, w)


@functools.partial(jax.jit, static_argnames=(
    "eps", "k", "normalize", "scale"))
def _routed(x, norms, w, chosen, *, eps, k, normalize, scale):
    """Steps 4-5 of a routed layer.  ``chosen [T, k]`` are the experts
    to apply, ``None`` for the reference's own top-k.  Also returns, for
    every choice, how far its selection score lies below the k-th
    largest, and whether it is outside the reference's own top-k."""
    h = _rms_norm(x, norms["post_attention_layernorm"].astype(jnp.float32),
                  eps)
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
    return x + m, shortfall, not_first


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
        for layer in weights["layers"]:
            x = _attention(
                x, layer["norms"], layer["attn"],
                heads=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                v_dim=cfg["v_head_dim"], eps=eps,
                theta=float(cfg["rope_theta"]))
            if "dense" in layer:
                x = _dense(x, layer["norms"], layer["dense"], eps=eps)
                continue
            x, s, n = _routed(
                x, layer["norms"], layer["routed"],
                None if chosen is None else chosen[routed], eps=eps,
                k=cfg["num_experts_per_tok"],
                normalize=bool(cfg.get("norm_topk_prob", True)),
                scale=float(cfg.get("routed_scaling_factor", 1.0)))
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
    k, experts = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
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
