"""Prompt tokens the radix prefix index served instead of a prefill,
over the prompt tokens submitted in the window (engine counters)."""


def read(rec):
    c = rec["counters"]
    n = c["prompt_tokens_submitted"]
    return 100.0 * c["prefix_hit_tokens"] / n if n else None
