"""Plain reference of the tests' routed toy (``toy_routed.py``): the same
layer equations in float32 ``jax.numpy`` at ``highest`` precision, one
row at a time, nothing of the program imported.  The weights are the
model's own arrays, read by its parameter names and widened as used.

With ``witness=`` (the experts the timed path chose, ``[L, B, T, k]`` or
``[L, 1, T, k]`` for one row) it REPLAYS those choices and VERIFIES
each against its own arithmetic, and returns ``(want, report)``.

``MARGIN``: a chosen expert's float32 score may lie this far below the
reference's own k-th best.  Scores are sigmoids, range 1, and the served
path forms them from a bf16 residual stream: 8 bf16 epsilons (2^-8
each) of that range, the same allowance ``LOGIT_TOL`` gives a logit.
Sound runs of the toy read a largest shortfall of 0.0050 over 16 seeds
and a witness that names the last-ranked expert 0.35 to 0.52 (PERF.md
section 4): the margin lies between, nearer the lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MARGIN = 2.0 ** -5
_STACKED = ("norms", "conv", "gate_in", "router", "w_gate", "w_up",
            "w_down")


def weights_of(model) -> dict:
    named = {n: p._value for n, p in model.named_parameters()}
    layers = named["norms"].shape[0]
    return {"embed": named["embed"], "norm": named["final_norm"],
            "head": named["head"],
            "layers": [tuple(named[k][i] for k in _STACKED)
                       for i in range(layers)]}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


@functools.partial(jax.jit, static_argnames=("k", "eps"))
def _layer(x, layer, chosen, *, k, eps):
    """One layer over ``x [T, H]``.  ``chosen [T, k]`` are the experts to
    apply, ``None`` for the reference's own top-k.  Also returns, for
    every choice, how far its score lies below the k-th best and
    whether it is outside the reference's own top-k."""
    norms, conv, gate_in, router, w_gate, w_up, w_down = [
        w.astype(jnp.float32) for w in layer]
    h = _rms(x, norms[0], eps)
    taps = conv.shape[0]
    padded = jnp.pad(h, ((taps - 1, 0), (0, 0)))
    mixed = sum(padded[j:j + h.shape[0]] * conv[j] for j in range(taps))
    x = x + mixed * jax.nn.sigmoid(h @ gate_in)
    h = _rms(x, norms[1], eps)
    scores = jax.nn.sigmoid(h @ router)
    best, own = jax.lax.top_k(scores, k)
    if chosen is None:
        chosen = own
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    shortfall = jnp.maximum(best[:, -1:] - picked, 0.0)
    not_first = (chosen[:, :, None] != own[:, None, :]).all(-1)
    gates = picked / picked.sum(-1, keepdims=True)
    g = jnp.einsum("th,ehm->tem", h, w_gate)
    u = jnp.einsum("th,ehm->tem", h, w_up)
    out = jnp.einsum("tem,emh->teh", jax.nn.silu(g) * u, w_down)
    out = jnp.take_along_axis(out, chosen[..., None], axis=1)
    return x + (out * gates[..., None]).sum(axis=1), shortfall, not_first


def _row(weights, cfg, tokens, last, chosen):
    """``(logits [last, V], shortfalls, not-first flags)`` of one row;
    ``chosen`` is ``[L, T, k]`` or ``None``."""
    k, eps = cfg["num_experts_per_tok"], cfg["norm_eps"]
    shortfalls, not_first = [], []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for i, layer in enumerate(weights["layers"]):
            x, s, n = _layer(
                x, layer, None if chosen is None else chosen[i],
                k=k, eps=eps)
            shortfalls.append(s)
            not_first.append(n)
        x = _rms(x[-last:], weights["norm"].astype(jnp.float32), eps)
        return (x @ weights["head"].astype(jnp.float32),
                jnp.stack(shortfalls), jnp.stack(not_first))


def _admissible(witness, cfg, layers, rows, length):
    """The witness as ``[L, B, T, k]`` int32 where it names ``k``
    different experts that exist at every decision of these rows, else
    ``None``."""
    w = np.asarray(witness)
    k, experts = cfg["num_experts_per_tok"], cfg["num_experts"]
    if w.shape != (layers, rows, length, k) or w.dtype.kind not in "iu":
        return None
    if w.min() < 0 or w.max() >= experts:
        return None
    ordered = np.sort(w, axis=-1)
    if (ordered[..., 1:] == ordered[..., :-1]).any():
        return None
    return w.astype(np.int32)


def _report(shortfalls, not_first):
    worst = max((float(s.max()) for s in shortfalls), default=0.0)
    return {"ok": bool(shortfalls) and worst <= MARGIN,
            "decisions": int(sum(s.size for s in shortfalls)),
            "not_first_choice": int(sum(int(n.sum()) for n in not_first)),
            "largest_shortfall": worst, "margin": MARGIN}


# (1.0: a sigmoid's whole range; every number of a report is finite, so
# that the line it is printed in stays JSON)
_INADMISSIBLE = {"ok": False, "decisions": 0, "not_first_choice": 0,
                 "largest_shortfall": 1.0, "margin": MARGIN}


def logits(weights, cfg, tokens, last, witness=None):
    """float32 logits ``[last, V]`` of the final ``last`` positions of
    the 1-D sequence ``tokens``; with a witness ``(logits, report)``."""
    if witness is None:
        return _row(weights, cfg, tokens, last, None)[0]
    w = _admissible(witness, cfg, len(weights["layers"]), 1, len(tokens))
    if w is None:
        return _row(weights, cfg, tokens, last, None)[0], _INADMISSIBLE
    want, s, n = _row(weights, cfg, tokens, last, jnp.asarray(w[:, 0]))
    return want, _report([s], [n])


def causal_lm_loss(weights, cfg, batch, witness=None):
    """Mean next-token cross-entropy over a ``[B, T]`` batch, one row at
    a time; with a witness ``(loss, report)``."""
    batch = np.asarray(batch)
    w = None
    if witness is not None:
        w = _admissible(witness, cfg, len(weights["layers"]), *batch.shape)
    total, count, shortfalls, not_first = 0.0, 0, [], []
    for b, row in enumerate(batch):
        lg, s, n = _row(weights, cfg, row, len(row),
                        None if w is None else jnp.asarray(w[:, b]))
        logp = jax.nn.log_softmax(lg[:-1], axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.asarray(row[1:])[:, None].astype(jnp.int32), axis=-1)
        total += float(-picked.sum())
        count += len(row) - 1
        shortfalls.append(s)
        not_first.append(n)
    if witness is None:
        return total / count
    if w is None:
        return total / count, _INADMISSIBLE
    return total / count, _report(shortfalls, not_first)
