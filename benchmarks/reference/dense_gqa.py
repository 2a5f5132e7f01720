"""Plain reference of a dense decoder-only LM with grouped-query
attention, as the published Mistral/Llama-style ``config.json`` describes
it: pre-norm residual blocks, RMSNorm, rotate-half RoPE on q and k,
causal softmax attention with ``num_key_value_heads`` shared by groups of
query heads, SiLU-gated MLP, a final norm and an untied output head.

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")`` (on a TPU a float32 product is
otherwise computed in bf16): no kernel, no cache, no batching, the whole
sequence in one forward pass.  Each layer's weights are widened from the
type they are served in as the layer is used, so no second model is held.

Departures from the published description: none in the mathematics.  The
weights are the program's own arrays, read by the parameter names of
``paddle_tpu.models.LlamaForCausalLM`` (linear weights are ``[in, out]``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LAYER_KEYS = ("input_layernorm.weight", "self_attn.q_proj.weight",
               "self_attn.k_proj.weight", "self_attn.v_proj.weight",
               "self_attn.o_proj.weight", "post_attention_layernorm.weight",
               "mlp.gate_proj.weight", "mlp.up_proj.weight",
               "mlp.down_proj.weight")


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


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta"))
def _layer(x, layer, *, heads, kv_heads, eps, theta):
    (ln1, wq, wk, wv, wo, ln2, wg, wu, wd) = [
        w.astype(jnp.float32) for w in layer]
    t = x.shape[0]
    d = wq.shape[1] // heads
    h = _rms_norm(x, ln1, eps)
    q = _rope((h @ wq).reshape(t, heads, d), theta)
    k = _rope((h @ wk).reshape(t, kv_heads, d), theta)
    v = (h @ wv).reshape(t, kv_heads, d)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, heads * d) @ wo
    h = _rms_norm(x, ln2, eps)
    return x + (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


@functools.partial(jax.jit, static_argnames=("eps", "last"))
def _head(x, norm, head, *, eps, last):
    x = _rms_norm(x[-last:], norm.astype(jnp.float32), eps)
    return x @ head.astype(jnp.float32)


def logits(weights, cfg, tokens, last):
    """float32 logits ``[last, V]`` of the final ``last`` positions of
    the 1-D sequence ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for layer in weights["layers"]:
            x = _layer(x, layer, heads=cfg["num_attention_heads"],
                       kv_heads=cfg["num_key_value_heads"],
                       eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"])
        return _head(x, weights["norm"], weights["head"],
                     eps=cfg["rms_norm_eps"], last=last)


def causal_lm_loss(weights, cfg, batch):
    """Mean next-token cross-entropy over a ``[B, T]`` batch (labels are
    the inputs shifted by one, every position but the last predicts),
    one row at a time."""
    total, count = 0.0, 0
    for row in batch:
        lg = logits(weights, cfg, row, last=len(row))[:-1]
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.asarray(row[1:])[:, None].astype(jnp.int32), axis=-1)
        total += float(-picked.sum())
        count += len(row) - 1
    return total / count
