"""The tests' own model that makes discrete choices: a small causal LM
whose every layer is a gated short causal convolution and a dropless
mixture of experts behind a normalised-sigmoid top-k router, held and
computed in bf16.  It stands where a later ``model_config`` PR's model
class stands: a configuration file names it, its witness function, its
plain reference (``toy_routed_reference.py``) and its cost counts
(``toy_routed_costs.py``) by path, and the harness runs it with no file
of its own edited.

The whole forward pass is one pure function under one ``apply``, so the
program's tape differentiates it as it stands.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.nn import initializer as I


@dataclasses.dataclass
class ToyRoutedConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_experts: int = 16
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 32
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # a planted fault for the tests: in the last layer the weights of
    # expert ``(e + apply_shift) % num_experts`` are applied where ``e``
    # was chosen and is reported
    apply_shift: int = 0


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _forward(tokens, embed, norms, conv, gate_in, router, w_gate, w_up,
             w_down, final_norm, head, *, k, eps, shift, ranking):
    """``tokens [B, T]`` -> logits ``[B, T, V]`` in the weights' type, or
    with ``ranking`` every layer's experts in the router's order, best
    first, ``[L, B, T, E]``."""
    x = embed[tokens]
    ranked = []
    for i in range(norms.shape[0]):
        # gated short convolution over this and the earlier positions
        h = _rms(x, norms[i, 0], eps)
        taps = conv.shape[1]
        padded = jnp.pad(h, ((0, 0), (taps - 1, 0), (0, 0)))
        mixed = sum(padded[:, j:j + h.shape[1]] * conv[i, j]
                    for j in range(taps))
        x = x + mixed * jax.nn.sigmoid(h @ gate_in[i])
        # normalised-sigmoid top-k router, dropless experts
        h = _rms(x, norms[i, 1], eps)
        scores = jax.nn.sigmoid(
            h.astype(jnp.float32) @ router[i].astype(jnp.float32))
        order = jnp.argsort(-scores, axis=-1).astype(jnp.int32)
        ranked.append(order)
        chosen = order[..., :k]
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = (gates / gates.sum(-1, keepdims=True)).astype(x.dtype)
        applied = chosen
        if i == norms.shape[0] - 1:
            applied = (chosen + shift) % router.shape[-1]
        g = jnp.einsum("bth,ehm->btem", h, w_gate[i])
        u = jnp.einsum("bth,ehm->btem", h, w_up[i])
        out = jnp.einsum("btem,emh->bteh", jax.nn.silu(g) * u, w_down[i])
        out = jnp.take_along_axis(out, applied[..., None], axis=2)
        x = x + (out * gates[..., None]).sum(axis=2)
    if ranking:
        return jnp.stack(ranked)
    return _rms(x, final_norm, eps) @ head


class ToyRoutedLM(nn.Layer):
    def __init__(self, config: ToyRoutedConfig):
        super().__init__()
        self.config = config
        c = config
        L, H, E, M = (c.num_hidden_layers, c.hidden_size, c.num_experts,
                      c.moe_intermediate_size)

        def normal(shape, fan_in, gain=1.0):
            return self.create_parameter(
                shape, default_initializer=I.Normal(
                    std=gain / math.sqrt(fan_in)))

        def ones(shape):
            return self.create_parameter(
                shape, default_initializer=I.Constant(1.0))

        self.embed = normal([c.vocab_size, H], 1.0)
        self.norms = ones([L, 2, H])
        self.conv = normal([L, c.conv_L_cache, H], c.conv_L_cache)
        self.gate_in = normal([L, H, H], H)
        self.router = normal([L, H, E], H)
        self.w_gate = normal([L, E, H, M], H)
        self.w_up = normal([L, E, H, M], H)
        self.w_down = normal([L, E, M, H], M)
        self.final_norm = ones([H])
        # small logits, as a model's are before it has learnt much: the
        # loss of a few hundred tokens then averages the bf16 roundings
        # of its logits down to what the mean over a real batch does
        self.head = normal([H, c.vocab_size], H, gain=0.1)
        if c.dtype == "bfloat16":
            self.bfloat16()

    def _run(self, tokens, ranking):
        c = self.config
        return apply(
            "toy_routed_lm", _forward, tokens, self.embed, self.norms,
            self.conv, self.gate_in, self.router, self.w_gate, self.w_up,
            self.w_down, self.final_norm, self.head,
            k=c.num_experts_per_tok, eps=c.norm_eps, shift=c.apply_shift,
            ranking=ranking)

    def forward(self, tokens, labels=None):
        logits = self._run(tokens, False)
        if labels is None:
            return logits

        def _loss(lg, lab):
            logp = jax.nn.log_softmax(lg[:, :-1].astype(jnp.float32), -1)
            picked = jnp.take_along_axis(
                logp, lab[:, 1:, None].astype(jnp.int32), axis=-1)
            return -jnp.mean(picked)

        return apply("causal_lm_loss", _loss, logits, labels), logits

    def ranking(self, tokens):
        """Every decision of the forward pass on ``tokens [B, T]`` and
        the weights held now: the experts in the router's order."""
        ranked = paddle.jit.to_static(lambda ids: self._run(ids, True))
        with paddle.no_grad():
            out = ranked(paddle.to_tensor(np.asarray(tokens, np.int32)))
        return np.asarray(out._value)


def _ranked(model, tokens):
    tokens = np.asarray(tokens)
    return model.ranking(tokens if tokens.ndim == 2 else tokens[None])


def witness(model, engine, tokens, block_table=None, prompt_tokens=None):
    """The experts the model's forward pass chooses for ``tokens`` (one
    row or a batch) on the weights it holds now: ``[L, B, T, k]``."""
    return _ranked(model, tokens)[..., :model.config.num_experts_per_tok]


def witness_naming_the_last(model, engine, tokens, **kw):
    """A witness that lies once: the last position's last slot in the
    first layer names the expert the router ranked LAST."""
    ranked = _ranked(model, tokens)
    chosen = ranked[..., :model.config.num_experts_per_tok].copy()
    chosen[0, 0, -1, -1] = ranked[0, 0, -1, -1]
    return chosen
