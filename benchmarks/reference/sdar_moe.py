"""Plain reference of a decoder that generates by diffusion over blocks,
with a norm on q and k and a routed layer of experts in every decoder
layer, as the published SDAR-30B-A3B-Chat ``config.json`` (``model_type``
``sdar_moe``) and the family's released generation loop describe it.

For a layer with input ``h [T, hidden]``:

1. ``a = RMSNorm(h)``; ``q = a.Wq`` (heads x head_dim), ``k = a.Wk``,
   ``v = a.Wv`` (kv heads x head_dim);
2. ``q <- RMSNorm(q)``, ``k <- RMSNorm(k)`` over the ``head_dim`` lanes of
   every head, one learned weight for q and one for k, then rotate-half
   RoPE;
3. ``h <- h + softmax(q.k^T / sqrt(head_dim) + M).v.Wo``, grouped
   queries, with the BLOCK-CAUSAL mask ``M[i, j] = 0`` where
   ``floor(j / L) <= floor(i / L)``, else -inf (``L = block_length``,
   positions absolute);
4. ``b = RMSNorm(h)``; ``p = softmax(b.Wr)`` over all experts; the
   ``num_experts_per_tok`` largest; ``g = p_k / sum p_k``
   (``norm_topk_prob``); ``h <- h + sum_e g_e . (silu(b.Wgate_e) *
   (b.Wup_e)).Wdown_e``.  No capacity, no dropped token, no shared
   expert;
5. after the last layer ``RMSNorm``, then the untied head.  The logits at
   position ``i`` are for the token AT ``i`` (a mask is predicted in
   place: no shift).

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: one full forward over a whole
token row (masks included, as their token id), no cache, no kernel, no
batching; a token's experts are gathered and applied one token at a
time.  Weights are widened from the type they are served in as they are
used.

``remask`` is the generation loop's choice of what a denoise step
unmasks, both rules, as a plain function of logits and mask.

With ``witness=`` (the experts the served path chose, ``[layers, T, k]``
int32) ``logits`` REPLAYS those choices and VERIFIES each against its own
arithmetic, and returns ``(want, report)``.

``MARGIN``: a chosen expert's own router logit ``(b.Wr)_e`` may lie this
far, as a share of the largest router logit of the row in magnitude,
under the reference's k-th largest.  The served path forms its router
logits from a residual stream rounded to bf16 at every layer boundary;
``2^-5`` is eight bf16 epsilons, the allowance ``LOGIT_TOL`` gives an
output logit for the same reason.  (On the softmax's scale the same
gap is that many e-folds of probability; the logits are what rounding
moves.)  Measured: PERF.md section 4.

Departures from the published description: the config gives neither
the q/k norm, nor the block length, nor the no-shift reading, nor the
loop; they are the base architecture's and the family's released code
as the configuration's ``assumed`` lists them.  The weights are the
program's own arrays, read by the parameter names of
``paddle_tpu.models.SDARMoEForCausalLM`` (projections ``[in, out]``;
expert matrices ``[experts, expert_width, hidden]`` for gate, up and the
transposed down).  ``causal_lm_loss`` is absent on purpose: the model is
served only, and a diffusion objective is not a next-token loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MARGIN = 2.0 ** -5
_LAYER_KEYS = ("input_layernorm.weight", "self_attn.q_proj.weight",
               "self_attn.k_proj.weight", "self_attn.v_proj.weight",
               "self_attn.o_proj.weight", "self_attn.q_norm.weight",
               "self_attn.k_norm.weight", "post_attention_layernorm.weight",
               "mlp.router", "mlp.w_gate", "mlp.w_up", "mlp.w_down")


def weights_of(model) -> dict:
    """``{"embed", "norm", "head", "layers": [tuple per layer]}`` of the
    arrays ``model`` holds now (no copy)."""
    named = {n: p._value for n, p in model.named_parameters()}
    n_layers = 1 + max(int(n.split(".")[2]) for n in named
                       if n.startswith("model.layers."))
    return {"embed": named["model.embed_tokens.weight"],
            "norm": named["model.norm.weight"],
            "head": named["lm_head.weight"],
            "layers": [tuple(named[f"model.layers.{i}.{k}"]
                             for k in _LAYER_KEYS)
                       for i in range(n_layers)]}


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


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta", "block", "k",
    "normalize"))
def _layer(x, layer, chosen, *, heads, kv_heads, head_dim, eps, theta,
           block, k, normalize):
    """One layer over ``x [T, hidden]``.  ``chosen [T, k]`` are the
    experts to apply, ``None`` for the reference's own top-k.  Also
    returns, for every choice, how far its router logit lies below the
    k-th largest, whether it is outside the reference's own top-k, and
    the largest router logit in magnitude."""
    (ln1, wq, wk, wv, wo, qn, kn, ln2, wr, wg, wu, wd) = layer
    ln1, wq, wk, wv, wo, qn, kn, ln2, wr = [
        w.astype(jnp.float32) for w in (ln1, wq, wk, wv, wo, qn, kn, ln2,
                                        wr)]
    t = x.shape[0]
    a = _rms_norm(x, ln1, eps)
    q = _rms_norm((a @ wq).reshape(t, heads, head_dim), qn, eps)
    kk = _rms_norm((a @ wk).reshape(t, kv_heads, head_dim), kn, eps)
    v = (a @ wv).reshape(t, kv_heads, head_dim)
    q, kk = _rope(q, theta), _rope(kk, theta)
    group = heads // kv_heads
    kk = jnp.repeat(kk, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(
        jnp.float32(head_dim))
    at = jnp.arange(t)
    see = (at[None, :] // block) <= (at[:, None] // block)
    scores = jnp.where(see[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, heads * head_dim) @ wo

    b = _rms_norm(x, ln2, eps)
    z = b @ wr                                          # router logits
    p = jax.nn.softmax(z, axis=-1)
    best, own = jax.lax.top_k(z, k)
    if chosen is None:
        chosen = own
    picked_z = jnp.take_along_axis(z, chosen, axis=-1)
    shortfall = jnp.maximum(best[:, -1:] - picked_z, 0.0)
    not_first = (chosen[:, :, None] != own[:, None, :]).all(-1)
    gates = jnp.take_along_axis(p, chosen, axis=-1)
    if normalize:
        gates = gates / gates.sum(-1, keepdims=True)

    def one_token(args):
        b_t, chosen_t, gates_t = args
        g = jnp.einsum("kmh,h->km", wg[chosen_t].astype(jnp.float32), b_t)
        u = jnp.einsum("kmh,h->km", wu[chosen_t].astype(jnp.float32), b_t)
        return jnp.einsum("km,kmh->h",
                          jax.nn.silu(g) * u * gates_t[:, None],
                          wd[chosen_t].astype(jnp.float32))

    out = jax.lax.map(one_token, (b, chosen, gates))
    return x + out, shortfall, not_first, jnp.max(jnp.abs(z))


@functools.partial(jax.jit, static_argnames=("eps", "last"))
def _head(x, norm, head, *, eps, last):
    x = _rms_norm(x[-last:], norm.astype(jnp.float32), eps)
    return x @ head.astype(jnp.float32)


def _row(weights, cfg, tokens, last, chosen):
    """``(logits [last, V], shortfalls, not-first flags, largest router
    logit)`` of one row; ``chosen`` is ``[layers, T, k]`` or ``None``."""
    shortfalls, not_first, scale = [], [], 0.0
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for i, layer in enumerate(weights["layers"]):
            x, s, n, z = _layer(
                x, layer, None if chosen is None else chosen[i],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
                theta=float(cfg["rope_theta"]),
                block=block_length(cfg), k=cfg["num_experts_per_tok"],
                normalize=bool(cfg.get("norm_topk_prob", True)))
            shortfalls.append(s)
            not_first.append(n)
            scale = max(scale, float(z))
        return (_head(x, weights["norm"], weights["head"],
                      eps=cfg["rms_norm_eps"], last=last),
                shortfalls, not_first, scale)


def block_length(cfg) -> int:
    return cfg.get("model_config_kwargs", {}).get("block_length") \
        or cfg["block_length"]


def _admissible(witness, cfg, layers, length):
    """The witness as ``[layers, T, k]`` int32 where it names ``k``
    different experts that exist at every position, else ``None``."""
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
    the 1-D row ``tokens`` (masks as their token id); with a witness
    ``(logits, report)``: ``ok`` (every choice within ``MARGIN`` times
    the largest router logit of the reference's k-th best),
    ``decisions``, ``not_first_choice``, ``largest_shortfall`` and
    ``margin`` (both in router-logit units)."""
    if witness is None:
        return _row(weights, cfg, tokens, last, None)[0]
    w = _admissible(witness, cfg, len(weights["layers"]), len(tokens))
    if w is None:
        # (every number of a report is finite: it is printed as JSON)
        return _row(weights, cfg, tokens, last, None)[0], {
            "ok": False, "decisions": 0, "not_first_choice": 0,
            "largest_shortfall": 1e9, "margin": MARGIN}
    want, shortfalls, not_first, scale = _row(weights, cfg, tokens, last,
                                              jnp.asarray(w))
    worst = max(float(s.max()) for s in shortfalls)
    return want, {
        "ok": worst <= MARGIN * scale,
        "decisions": int(sum(s.size for s in shortfalls)),
        "not_first_choice": int(sum(int(n.sum()) for n in not_first)),
        "largest_shortfall": worst, "margin": MARGIN * scale}


def remask(block_logits, masked, n_unmask, tau=None):
    """What one denoise step unmasks.  ``block_logits [L, V]``,
    ``masked [L]`` bool.  At every masked position the candidate is the
    argmax and its confidence the softmax probability of it.
    ``low_confidence_static`` (``tau`` None): the ``n_unmask`` masked
    positions of highest confidence.  ``low_confidence_dynamic``: every
    masked position whose confidence passes ``tau``, and the ``n_unmask``
    best where fewer pass.  Ties go to the earlier position.  Returns
    ``(unmask [L] bool, candidates [L], confidence [L])``."""
    lg = np.asarray(block_logits, np.float64)
    masked = np.asarray(masked, bool)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    cand, conf = p.argmax(-1), p.max(-1)
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
    unmask = np.zeros_like(masked)
    unmask[order[:n_unmask]] = True
    if tau is not None:
        unmask |= masked & (conf > tau)
    return unmask, cand, conf


def causal_lm_loss(weights, cfg, batch, witness=None):
    raise NotImplementedError(
        "this configuration is served only: generation by diffusion over "
        "blocks has no next-token loss to compare")
