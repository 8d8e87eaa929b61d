"""Share of the prompt tokens admitted in the window that were served
from shared pages: the pool's own counters, read when the window opens
and when it closes."""


def read(ctx):
    c0, c1 = ctx["counters"]
    prompts = c1["prompt_tokens"] - c0["prompt_tokens"]
    if prompts <= 0:
        return None
    return 100.0 * (c1["shared_tokens"] - c0["shared_tokens"]) / prompts
