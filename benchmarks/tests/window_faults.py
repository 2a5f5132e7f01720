"""Faults planted into the program that serves a model with window
layers, each as ``fault(setattr)`` with ``setattr(object, name, value)``
(a test's ``monkeypatch.setattr``, or ``planted`` below, which undoes
them).  ``test_window_cell.py`` plants them at tiny widths on the CPU;
``window_controls.py`` plants three at the cell's own sizes on the chip.
Every one must turn the cell's ``correct`` false."""
from __future__ import annotations

import contextlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np


@contextlib.contextmanager
def planted(fault):
    """``fault`` planted for the duration, then taken out again."""
    done = []

    def setattr_(obj, name, value):
        done.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    try:
        fault(setattr_)
        yield
    finally:
        for obj, name, value in reversed(done):
            setattr(obj, name, value)


def _kernels():
    from paddle_tpu.kernels import chunked_prefill, paged_attention

    return ((chunked_prefill, "fused_chunked_attention"),
            (paged_attention, "fused_paged_decode"))


def _with_window(setattr_, change):
    """Both serving kernels called with ``change(window, call index)``
    in place of a window layer's ``window``."""
    for module, name in _kernels():
        kernel, calls = getattr(module, name), itertools.count()

        def changed(*a, window=None, _kernel=kernel, _calls=calls, **kw):
            if window is not None:
                window = change(window, next(_calls))
            return _kernel(*a, window=window, **kw)

        setattr_(module, name, changed)


def window_layers_run_as_full_layers(setattr_):
    """Every window layer's kernels walk the whole context: they reach
    pages the manager took back long ago."""
    _with_window(setattr_, lambda window, call: None)


def the_window_left_out_of_one_layer(setattr_):
    """... of the first window layer alone (a program calls a kernel
    once a window layer, in layer order, when it is traced)."""
    _with_window(setattr_, lambda window, call: None if call == 0
                 else window)


def a_walk_that_starts_one_page_early(setattr_):
    """The walk and its mask begin a page (16 keys at the served block
    size) before the window: the page the manager has just taken back is
    still reached."""
    _with_window(setattr_, lambda window, call: window + 16)


def rope_on_the_full_layer(setattr_):
    from paddle_tpu.models.afmoe import AfmoeForCausalLM as M

    rotate = M._rotate
    setattr_(M, "_rotate", lambda self, layer, q, k, start: rotate(
        self, self.model.layers[0], q, k, start))


def the_attention_gate_left_out(setattr_):
    from paddle_tpu.models.afmoe import AfmoeForCausalLM as M

    project = M._projections

    def ungated(self, layer, x):
        q, k, v, g = project(self, layer, x)
        return q, k, v, jnp.full_like(g, 30.0)      # sigmoid -> 1

    setattr_(M, "_projections", ungated)


def the_shared_expert_dropped(setattr_):
    from paddle_tpu.models.afmoe import AfmoeDecoderLayer, AfmoeMLP

    run = AfmoeMLP.run
    init = AfmoeDecoderLayer.__init__

    def marked(self, config, index):
        init(self, config, index)
        if self.routed:
            self.shared_expert.dropped = True

    setattr_(AfmoeDecoderLayer, "__init__", marked)
    setattr_(AfmoeMLP, "run", lambda self, x, nw, eps: jnp.zeros_like(x)
             if getattr(self, "dropped", False) else run(self, x, nw, eps))


def the_bias_added_to_the_weights(setattr_):
    """A chosen expert's gate made from ``score + bias``: the bias is
    for the selection alone."""
    from paddle_tpu.kernels import moe_experts as me

    route = me.route_topk

    def biased(x, w, k, *, bias=None, normalize=True, scale=None,
               norm_eps=None, **kw):
        chosen, _ = route(x, w, k, bias=bias, normalize=normalize,
                          scale=scale, norm_eps=norm_eps, **kw)
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                   w.astype(jnp.float32))) + bias
        g = jnp.take_along_axis(s, chosen, axis=-1)
        return chosen, scale * g / (g.sum(-1, keepdims=True) + norm_eps)

    setattr_(me, "route_topk", biased)


def expert_weights_in(dtype):
    def fault(setattr_):
        from paddle_tpu.kernels import moe_experts as me

        grouped = me.grouped_experts
        setattr_(me, "grouped_experts",
                 lambda x, chosen, gates, *w, **kw: grouped(
                     x, chosen, gates,
                     *(m.astype(dtype).astype(m.dtype) for m in w), **kw))

    fault.__name__ = f"expert_weights_in_{jnp.dtype(dtype).name}"
    return fault


def a_witness_that_lies(setattr_):
    """The last position's last choice in the first routed layer names
    an expert the position did not choose."""
    from benchmarks.harness import models

    witness = models.witness

    def lying(config, **where):
        w = np.array(witness(config, **where))
        taken = set(w[0, -1].tolist())
        w[0, -1, -1] = max(e for e in range(config["num_experts"])
                           if e not in taken)
        return w

    setattr_(models, "witness", lying)


def reference_weights_in(dtype, in_place=False):
    """The REFERENCE computed from weights held in ``dtype``: the
    comparison must be tight enough to tell a precision below the one
    the configuration states.  ``in_place`` rounds the MODEL's own
    arrays, a tensor at a time, when the reference asks for them, which
    is after the served programs have run: at the published widths a
    second copy of the weights does not fit the chip.  The model is not
    to be served again after that."""
    def rounded(w):
        if not jnp.issubdtype(w.dtype, jnp.floating):
            return w
        return w.astype(dtype).astype(w.dtype)

    def fault(setattr_):
        import types

        from benchmarks.harness import models

        load = models.load_reference

        def weights_of(ref, model):
            if not in_place:
                return jax.tree_util.tree_map(rounded, ref.weights_of(model))
            for _, t in list(model.named_parameters()) \
                    + list(model.named_buffers()):
                t._value = rounded(t._value)
            return ref.weights_of(model)

        def narrowed(config):
            ref = load(config)
            return types.SimpleNamespace(
                logits=ref.logits,
                weights_of=lambda model: weights_of(ref, model))

        setattr_(models, "load_reference", narrowed)

    fault.__name__ = f"reference_weights_in_{jnp.dtype(dtype).name}"
    return fault
