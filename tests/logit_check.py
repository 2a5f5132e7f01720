"""Comparing logits, not greedy tokens: with seeded random weights the
two largest logits of a row often lie closer than any rounding moves
them, so an argmax flips where nothing is wrong and every later token
then differs.  A served path is held to its reference row by row, by
the largest difference as a share of the largest reference logit."""
import numpy as np


def assert_logits_within(got, want, share, what=""):
    """Every row of ``got`` within ``share`` of the largest ``|want|`` of
    ``want``; returns the largest difference as such a share."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    worst = float(np.abs(got - want).max() / np.abs(want).max())
    assert worst <= share, f"{what}: {worst:.3g} of the largest logit " \
        f"(limit {share:.3g})"
    return worst


def decided(want, share):
    """Rows of ``want`` whose argmax a difference of ``share`` of the
    largest logit cannot change: the best logit leads the second by more
    than twice that."""
    want = np.asarray(want, np.float64)
    top = np.sort(want, axis=-1)
    return top[..., -1] - top[..., -2] > 2 * share * np.abs(want).max()


def causal_engine_logits(eng, prompt, feed):
    """Logits ``[1 + len(feed), V]`` of one sequence through a causal
    engine's own step programs: the prompt's last position, then one
    decode step per fed token (teacher-forced), on blocks 1.. of its
    idle pool."""
    from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                              make_paged_decode_step)

    cfg = eng.config
    kw = dict(fused=cfg.fused_kernels, kv_cache_dtype=cfg.kv_cache_dtype)
    prefill = make_chunked_prefill_step(eng.model, **kw)
    decode = make_paged_decode_step(eng.model, **kw)
    C, S = eng.chunk_tokens, cfg.max_batch_size
    table = np.zeros((S, eng.max_blocks_per_seq), np.int32)
    n = -(-(len(prompt) + len(feed) + 1) // cfg.block_size)
    table[0, :n] = np.arange(1, n + 1)
    pools = eng.pool.layers
    for start in range(0, len(prompt), C):
        n_tok = min(C, len(prompt) - start)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n_tok] = prompt[start:start + n_tok]
        last, pools = prefill(ids, pools, table[:1],
                              np.asarray([start], np.int32),
                              np.int32(n_tok - 1))
    out = [np.asarray(last)[0]]
    lengths = np.zeros((S,), np.int32)
    lengths[0] = len(prompt)
    tok = np.zeros((S, 1), np.int32)
    for t in feed:
        tok[0, 0] = t
        logits, pools = decode(tok.copy(), pools, table, lengths.copy())
        out.append(np.asarray(logits)[0])
        lengths[0] += 1
    # a step consumes the pool it is handed: the engine gets the last
    eng.pool.layers = [tuple(entry) for entry in pools]
    return np.stack(out)
