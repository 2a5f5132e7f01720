"""Share of the prompt tokens admitted inside the window that the prefix
cache served from pages already in the pool: counters
``cached_prompt_tokens`` over ``prompt_tokens`` (both summed at
admission, ``serving/metrics.py``)."""


def read(run):
    counters = run.get("counters") or {}
    prompts = counters.get("prompt_tokens")
    if not prompts or "cached_prompt_tokens" not in counters:
        return None
    return 100.0 * counters["cached_prompt_tokens"] / prompts
