"""Llama model family + graft entry points."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.optimizer import AdamW


def tokens(b=2, t=16, vocab=256):
    return paddle.to_tensor(
        np.random.RandomState(0).randint(0, vocab, (b, t)).astype(np.int32))


class TestLlama:
    def test_forward_shapes(self):
        model = LlamaForCausalLM(LlamaConfig.tiny())
        logits = model(tokens())
        assert logits.shape == [2, 16, 256]

    def test_loss_and_grads(self):
        model = LlamaForCausalLM(LlamaConfig.tiny())
        loss, logits = model(tokens(), labels=tokens())
        loss.backward()
        assert model.model.layers[0].self_attn.q_proj.weight.grad is not None
        assert model.model.embed_tokens.weight.grad is not None

    def test_gqa_heads(self):
        cfg = LlamaConfig.tiny(num_attention_heads=4, num_key_value_heads=2)
        model = LlamaForCausalLM(cfg)
        assert model(tokens()).shape == [2, 16, 256]

    def test_compiled_training_learns(self):
        model = LlamaForCausalLM(LlamaConfig.tiny())
        opt = AdamW(1e-3, parameters=model.parameters())

        @jit.to_static
        def step(x):
            loss, _ = model(x, labels=x)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = tokens()
        losses = [float(step(x).numpy()) for _ in range(10)]
        assert losses[-1] < losses[0]

    def test_generate_greedy(self):
        model = LlamaForCausalLM(LlamaConfig.tiny())
        out = model.generate(tokens(t=4), max_new_tokens=3, temperature=0.0)
        assert out.shape == [2, 7]
        # prefix preserved
        np.testing.assert_array_equal(out.numpy()[:, :4], tokens(t=4).numpy())

    def test_tied_embeddings(self):
        cfg = LlamaConfig.tiny(tie_word_embeddings=True)
        model = LlamaForCausalLM(cfg)
        assert model(tokens()).shape == [2, 16, 256]

    def test_rope_rotation_identity_at_zero(self):
        from paddle_tpu.models.llama import apply_rope, precompute_rope
        import jax.numpy as jnp

        cos, sin = precompute_rope(8, 16, 10000.0)
        x = jnp.ones((1, 1, 2, 8))
        out = apply_rope(x, cos, sin, 0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)


class TestGraftEntry:
    def test_dryrun_multichip_8(self):
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "graft_entry",
            os.path.join(os.path.dirname(__file__), "..",
                         "__graft_entry__.py"))
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        m.dryrun_multichip(8)


class TestFusedLMLoss:
    def test_matches_unfused(self):
        cfg = LlamaConfig.tiny(fused_lm_loss=False)
        model = LlamaForCausalLM(cfg)
        loss_ref, _ = model(tokens(), labels=tokens())
        model.config.fused_lm_loss = True
        model.config.lm_loss_chunk = 7  # force multi-chunk + padding path
        loss_fused, logits = model(tokens(), labels=tokens())
        assert logits is None
        np.testing.assert_allclose(
            float(loss_ref.numpy()), float(loss_fused.numpy()), rtol=2e-3)

    def test_fused_grads_flow(self):
        model = LlamaForCausalLM(LlamaConfig.tiny(lm_loss_chunk=8))
        loss, _ = model(tokens(), labels=tokens())
        loss.backward()
        assert model.lm_head.weight.grad is not None
        assert model.model.embed_tokens.weight.grad is not None

    def test_fused_tied(self):
        model = LlamaForCausalLM(
            LlamaConfig.tiny(tie_word_embeddings=True, lm_loss_chunk=8))
        loss, _ = model(tokens(), labels=tokens())
        loss.backward()
        assert model.model.embed_tokens.weight.grad is not None


class TestGeneration:
    """KV-cache decoding (models/generation.py): greedy determinism,
    top-k/top-p sampling, beam search score dominance, eos stop."""

    def _model(self):
        paddle.seed(0)
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        return LlamaForCausalLM(LlamaConfig.tiny())

    def _score(self, model, seq, prompt_len):
        import jax
        import jax.numpy as jnp

        logits = model(paddle.to_tensor(seq[None].astype(np.int32)))
        logp = jax.nn.log_softmax(
            logits._value[0].astype(jnp.float32), -1)
        tot = 0.0
        for t in range(prompt_len - 1, seq.shape[0] - 1):
            tot += float(logp[t, seq[t + 1]])
        return tot

    def test_greedy_deterministic_and_matches_scores(self):
        model = self._model()
        ids = paddle.to_tensor(np.array([[1, 2, 3]], np.int32))
        a = model.generate(ids, max_new_tokens=5, temperature=0.0)
        b = model.generate(ids, max_new_tokens=5, temperature=0.0)
        np.testing.assert_array_equal(a.numpy(), b.numpy())
        assert a.shape == [1, 8]

    def test_beam_score_dominates_greedy(self):
        model = self._model()
        ids = np.array([[1, 2, 3]], np.int32)
        greedy = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                                temperature=0.0).numpy()[0]
        beam = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                              num_beams=4, do_sample=False).numpy()[0]
        s_g = self._score(model, greedy, 3)
        s_b = self._score(model, beam, 3)
        assert s_b >= s_g - 1e-4, (s_b, s_g)

    def test_sampling_seeded_reproducible(self):
        model = self._model()
        ids = paddle.to_tensor(np.array([[1, 2, 3]], np.int32))
        a = model.generate(ids, max_new_tokens=4, temperature=0.9,
                           top_k=8, top_p=0.95, seed=7)
        b = model.generate(ids, max_new_tokens=4, temperature=0.9,
                           top_k=8, top_p=0.95, seed=7)
        np.testing.assert_array_equal(a.numpy(), b.numpy())

    def test_eos_early_stop_pads_with_eos(self):
        model = self._model()
        ids = np.array([[1, 2, 3]], np.int32)
        g = model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                           temperature=0.0).numpy()
        eos = int(g[0, 3])  # force the first generated token to be "eos"
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             temperature=0.0, eos_token_id=eos).numpy()
        assert out.shape[1] < 3 + 6 or (out[0, 4:] == eos).all()

    def test_cached_prefill_is_causal(self):
        """Regression: prefill THROUGH the kv cache must produce the same
        logits as the no-cache causal forward (the old cache path attended
        bidirectionally during prefill, corrupting every generation)."""
        from paddle_tpu.models.generation import _empty_caches

        model = self._model()
        ids = paddle.to_tensor(np.random.RandomState(0).randint(
            0, 100, (2, 6)).astype(np.int32))
        ref = model(ids)
        caches = _empty_caches(model, 2)
        lg, _ = model(ids, caches=caches, position_offset=0)
        np.testing.assert_allclose(lg.numpy(), ref.numpy(), atol=1e-5)

    def test_static_cache_matches_grow_cache(self):
        model = self._model()
        ids = paddle.to_tensor(np.random.RandomState(1).randint(
            0, 100, (2, 4)).astype(np.int32))
        grow = model.generate(ids, max_new_tokens=6, temperature=0.0)
        static = model.generate(ids, max_new_tokens=6, temperature=0.0,
                                use_static_cache=True)
        np.testing.assert_array_equal(grow.numpy(), static.numpy())

    def test_beam_static_cache_matches_grow_cache(self):
        """VERDICT r2 #3 done bar: static-cache beam search == dynamic-cache
        beam search token-for-token (the compiled step re-indexes the
        preallocated caches by beam parents inside the jit)."""
        model = self._model()
        ids = paddle.to_tensor(np.random.RandomState(2).randint(
            0, 100, (2, 4)).astype(np.int32))
        grow = model.generate(ids, max_new_tokens=6, num_beams=3,
                              do_sample=False)
        static = model.generate(ids, max_new_tokens=6, num_beams=3,
                                do_sample=False, use_static_cache=True)
        np.testing.assert_array_equal(grow.numpy(), static.numpy())

    def test_beam_one_matches_greedy(self):
        """num_beams=1 beam search degenerates to greedy decoding (both
        cache modes)."""
        from paddle_tpu.models.generation import _beam_generate

        model = self._model()
        ids = np.random.RandomState(3).randint(0, 100, (2, 4)).astype(
            np.int32)
        greedy = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                                temperature=0.0).numpy()
        for static in (False, True):
            beam1 = _beam_generate(model, ids, 5, 1, None,
                                   use_static_cache=static)
            np.testing.assert_array_equal(beam1.numpy(), greedy)

    def test_beam_static_cache_eos(self):
        """eos early-stop in static-cache beam search matches dynamic."""
        model = self._model()
        ids = np.array([[1, 2, 3]], np.int32)
        g = model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                           num_beams=2, do_sample=False).numpy()
        eos = int(g[0, 3])
        a = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                           num_beams=2, do_sample=False,
                           eos_token_id=eos).numpy()
        b = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                           num_beams=2, do_sample=False, eos_token_id=eos,
                           use_static_cache=True).numpy()
        np.testing.assert_array_equal(a, b)

    def test_decode_step_serves_rebound_weights(self):
        """ADVICE r2 (medium): generation after a weight update (training
        step, set_state_dict) must NOT serve the weights from before it.
        The compiled decode step takes the weights as arguments, so the
        SAME step serves the new ones, and compiles nothing for it."""
        from paddle_tpu.models.generation import make_decode_step

        model = self._model()
        ids = paddle.to_tensor(np.array([[1, 2, 3]], np.int32))
        model.generate(ids, max_new_tokens=4, temperature=0.0,
                       use_static_cache=True)
        step = make_decode_step(model)
        compiled = step._cache_size()
        assert compiled == 1
        # rebind weights to shifted values (as set_state_dict would)
        sd = {k: v.numpy() + 0.05 for k, v in model.state_dict().items()}
        model.set_state_dict(sd)
        out = model.generate(ids, max_new_tokens=4, temperature=0.0,
                             use_static_cache=True).numpy()
        assert make_decode_step(model) is step
        assert step._cache_size() == compiled
        ref = model.generate(ids, max_new_tokens=4, temperature=0.0).numpy()
        np.testing.assert_array_equal(out, ref)

    def test_static_cache_shapes_constant(self):
        """The whole point of StaticKVCache: every decode step reuses one
        buffer shape (growing shapes would recompile per token on TPU)."""
        from paddle_tpu.models.generation import _static_caches

        model = self._model()
        ids = paddle.to_tensor(np.array([[1, 2, 3]], np.int32))
        caches = _static_caches(model, 1, 8)
        shape0 = tuple(caches[0].k.shape)
        logits, caches = model(ids, caches=caches, position_offset=0)
        for t in range(3, 7):
            tok = paddle.to_tensor(np.array([[5]], np.int32))
            logits, caches = model(tok, caches=caches, position_offset=t)
            assert tuple(caches[0].k.shape) == shape0

    def test_decode_step_single_executable(self):
        """All decode positions share ONE compiled program (the traced
        offset + fixed cache shapes make retraces impossible)."""
        from paddle_tpu.models.generation import (_static_caches,
                                                  make_decode_step)

        model = self._model()
        step = make_decode_step(model)
        caches = [(c.k, c.v) for c in _static_caches(model, 2, 12)]
        for t in range(4, 10):
            last, caches = step(np.ones((2, 1), np.int32), caches,
                                np.int32(t))
        assert step._cache_size() == 1
        assert last.shape == (2, model.config.vocab_size)


class TestTermination:
    """EOS + stop-sequence termination in generate() (shared with the
    serving scheduler via models.generation.match_stop)."""

    def _model(self):
        paddle.seed(0)
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        return LlamaForCausalLM(LlamaConfig.tiny())

    def test_mixed_length_eos_pads_and_exits_early(self):
        """Regression: a batch where rows hit eos at DIFFERENT steps
        must pad each finished row with eos while the others keep
        decoding — and exit the loop the moment all rows are done
        instead of paying max_new_tokens of compute."""
        model = self._model()
        ids = paddle.to_tensor(np.random.RandomState(4).randint(
            0, 100, (2, 4)).astype(np.int32))
        ref = model.generate(ids, max_new_tokens=10,
                             temperature=0.0).numpy()
        # eos = row0's 2nd generated token; row1 continues past it
        eos = int(ref[0, 5])
        assert eos not in ref[1, 4:6], "seed picked a degenerate stream"
        out = model.generate(ids, max_new_tokens=10, temperature=0.0,
                             eos_token_id=eos).numpy()
        # row0: matches the reference through its eos, eos-padded after
        np.testing.assert_array_equal(out[0, :6], ref[0, :6])
        assert (out[0, 6:] == eos).all()
        # row1: termination of row0 must not perturb its stream
        np.testing.assert_array_equal(out[1, :out.shape[1]],
                                      ref[1, :out.shape[1]])
        if eos not in ref[1, 4:]:
            # row1 never finishes -> the loop ran to max_new_tokens
            assert out.shape[1] == 4 + 10

    def test_eos_early_exit_shortens_output(self):
        model = self._model()
        ids = paddle.to_tensor(np.array([[7, 8, 9]], np.int32))
        ref = model.generate(ids, max_new_tokens=8,
                             temperature=0.0).numpy()
        gen = ref[0, 3:]
        # a later token value != the first, so eos fires mid-stream
        eos = next(int(t) for t in gen[1:] if t != gen[0])
        k = int(np.where(gen == eos)[0][0])  # first occurrence
        assert 0 < k < 7, "seed picked a degenerate stream"
        out = model.generate(ids, max_new_tokens=8, temperature=0.0,
                             eos_token_id=eos).numpy()
        assert out.shape[1] == 3 + k + 1  # exited early at the eos
        np.testing.assert_array_equal(out[0], ref[0, :3 + k + 1])

    def test_stop_sequence_token_ids(self):
        model = self._model()
        ids = paddle.to_tensor(np.array([[5, 6, 7, 8]], np.int32))
        ref = model.generate(ids, max_new_tokens=8,
                             temperature=0.0).numpy()
        stop = [int(ref[0, 5]), int(ref[0, 6])]  # generated bigram
        out = model.generate(ids, max_new_tokens=8, temperature=0.0,
                             stop_sequences=[stop]).numpy()
        assert out.shape[1] == 7  # stopped right after the bigram
        np.testing.assert_array_equal(out[0], ref[0, :7])

    def test_stop_sequence_string_with_tokenizer(self):
        class Tok:
            def encode(self, s):
                return [ord(c) % 256 for c in s]

        model = self._model()
        ids = paddle.to_tensor(np.array([[5, 6, 7, 8]], np.int32))
        ref = model.generate(ids, max_new_tokens=6,
                             temperature=0.0).numpy()
        text = chr(int(ref[0, 5]))  # 1st generated token as a "string"
        out = model.generate(ids, max_new_tokens=6, temperature=0.0,
                             stop_sequences=text, tokenizer=Tok()).numpy()
        assert out.shape[1] == 6
        np.testing.assert_array_equal(out[0], ref[0, :6])

    def test_stop_sequences_rejected_with_beam_search(self):
        model = self._model()
        ids = paddle.to_tensor(np.array([[1, 2, 3]], np.int32))
        with pytest.raises(ValueError, match="beam"):
            model.generate(ids, max_new_tokens=3, num_beams=2,
                           do_sample=False, stop_sequences=[[1]])

    def test_normalize_and_match_stop_helpers(self):
        from paddle_tpu.models.generation import (match_stop,
                                                  normalize_stop_sequences)

        assert normalize_stop_sequences(None) == []
        assert normalize_stop_sequences(7) == [[7]]
        assert normalize_stop_sequences([1, 2]) == [[1, 2]]
        assert normalize_stop_sequences([[1, 2], 3]) == [[1, 2], [3]]
        with pytest.raises(ValueError, match="tokenizer"):
            normalize_stop_sequences("stop")
        with pytest.raises(ValueError, match="empty"):
            normalize_stop_sequences([[]])
        assert match_stop([4, 1, 2], [[1, 2]])
        assert not match_stop([1, 2, 4], [[1, 2]])
        assert not match_stop([2], [[1, 2]])
