"""Faults planted into the program that serves a model with latent
(MLA) pages, each as ``fault(setattr)`` with ``setattr(object, name,
value)`` (a test's ``monkeypatch.setattr``, or ``window_faults.planted``,
which undoes them).  ``test_reask_cell.py`` plants them at tiny widths on
the CPU; ``reask_controls.py`` plants three at the cell's own sizes on
the chip.  Every one must turn the cell's ``correct`` false."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.tests.window_faults import (  # noqa: F401  (shared faults)
    expert_weights_in, planted, reference_weights_in,
    the_bias_added_to_the_weights)


def _model():
    from paddle_tpu.models.glm4_moe_lite import Glm4MoeLiteForCausalLM

    return Glm4MoeLiteForCausalLM


def the_latent_norm_left_out(setattr_):
    """``c_kv`` cached as ``h.W_kva`` came, without its RMSNorm."""
    from paddle_tpu.models import glm4_moe_lite as module

    rms = module._rms
    M = _model()
    latents = M._latents

    def without(self, layer, x, start):
        rank = self.config.kv_lora_rank
        setattr(module, "_rms", lambda v, w, eps: v * w
                if v.shape[-1] == rank else rms(v, w, eps))
        try:
            return latents(self, layer, x, start)
        finally:
            setattr(module, "_rms", rms)

    setattr_(M, "_latents", without)


def the_rotary_key_not_rotated(setattr_):
    """The one shared rotary key cached as it came, in every program."""
    setattr_(_model(), "_rotate_key",
             staticmethod(lambda k_r, cos, sin, start: k_r))


def the_rotary_key_not_rotated_in_the_decode_program(setattr_):
    """... in the decode program alone: the prompt's keys are sound, a
    decoded token's is not."""
    M = _model()
    rotate, decode = M._rotate_key, M.decode_token

    def decode_unrotated(self, tok, pools, table, lengths):
        M._rotate_key = staticmethod(lambda k_r, cos, sin, start: k_r)
        try:
            return decode(self, tok, pools, table, lengths)
        finally:
            M._rotate_key = staticmethod(rotate)

    setattr_(M, "decode_token", decode_unrotated)


def the_value_read_from_the_wrong_lanes(setattr_):
    """Both kernels take as the value the key's lanes 16.. in place of
    its first ones."""
    from paddle_tpu.kernels import latent_attention as la

    for name in ("fused_latent_decode", "fused_latent_chunk"):
        kernel = getattr(la, name)

        def shifted(*a, value_lanes, _kernel=kernel, **kw):
            out = _kernel(*a, value_lanes=value_lanes + 16, **kw)
            if isinstance(out, tuple):
                return out[0][..., 16:], out[1]
            return out[..., 16:]

        setattr_(la, name, shifted)


def routed_scaling_factor_dropped(setattr_):
    from paddle_tpu.kernels import moe_experts as me

    route = me.route_topk
    setattr_(me, "route_topk", lambda x, w, k, *, scale=None, **kw: route(
        x, w, k, scale=None, **kw))


def the_shared_expert_dropped(setattr_):
    from paddle_tpu.models.afmoe import AfmoeMLP
    from paddle_tpu.models.glm4_moe_lite import Glm4MoeLiteDecoderLayer

    run, init = AfmoeMLP.run, Glm4MoeLiteDecoderLayer.__init__

    def marked(self, config, index):
        init(self, config, index)
        if self.routed:
            self.shared_experts.dropped = True

    setattr_(Glm4MoeLiteDecoderLayer, "__init__", marked)
    setattr_(AfmoeMLP, "run", lambda self, x, nw, eps: jnp.zeros_like(x)
             if getattr(self, "dropped", False) else run(self, x, nw, eps))


def a_stale_page_matched(setattr_):
    """Row B is handed the pages of ANOTHER sequence: the match ignores
    the content's hash and returns as many blocks, a later one in place
    of the first (what a collision of hashes, or an index entry that
    outlived its block's content, would do)."""
    from paddle_tpu.serving.cache import BlockKVPool

    match = BlockKVPool.match_prefix

    def stale(self, tokens):
        found = match(self, tokens)
        return found[1:2] + found[1:] if len(found) > 1 else found

    setattr_(BlockKVPool, "match_prefix", stale)


def a_witness_that_lies(setattr_):
    """The last position's last choice in the first routed layer names
    an expert the position did not choose."""
    from benchmarks.harness import models

    witness = models.witness

    def lying(config, **where):
        w = np.array(witness(config, **where))
        taken = set(w[0, -1].tolist())
        w[0, -1, -1] = max(e for e in range(config["n_routed_experts"])
                           if e not in taken)
        return w

    setattr_(models, "witness", lying)
