"""Prompt tokens the prefix cache restored (first-chunk annotation cached_tokens) over prompt tokens sent."""


def read(ctx):
    rs = [r for r in ctx.requests if r["cached_tokens"] is not None]
    sent = sum(r["prompt_tokens"] for r in rs)
    return 100.0 * sum(r["cached_tokens"] for r in rs) / sent if sent else None
