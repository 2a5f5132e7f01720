"""The reference the tests' served configuration names: the dense
reference beside it for the logits and, given a witness (the first
layer's cached values of the checked tokens, ``toy_served.witness``),
its own float32 values of those tokens to hold the witness to.

``MARGIN``: the largest difference allowed between a cached value and
the reference's own, as a share of the largest: 8 bf16 epsilons, as
``LOGIT_TOL``."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

MARGIN = 2.0 ** -5

_spec = importlib.util.spec_from_file_location(
    "bench_dense_gqa", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "dense_gqa.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)
weights_of = _dense.weights_of


def logits(weights, cfg, tokens, last, witness=None):
    want = _dense.logits(weights, cfg, tokens, last)
    if witness is None:
        return want
    ln1, _, _, wv = [w.astype(jnp.float32)
                     for w in weights["layers"][0][:4]]
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        own = np.asarray(_dense._rms_norm(x, ln1, cfg["rms_norm_eps"]) @ wv)
    got = np.asarray(witness["values"], np.float32).reshape(len(tokens), -1)
    shortfall = float(np.abs(got - own).max() / np.abs(own).max()) \
        if got.shape == own.shape else 1.0
    return want, {"ok": shortfall <= MARGIN, "decisions": len(tokens),
                  "not_first_choice": 0, "largest_shortfall": shortfall,
                  "margin": MARGIN}


def causal_lm_loss(weights, cfg, batch, witness=None):
    return _dense.causal_lm_loss(weights, cfg, batch)
