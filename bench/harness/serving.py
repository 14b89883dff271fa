"""What a serving run's host records say about the traced window: the
prompt tokens prefilled, the prompts finished, the tokens decoded."""
from __future__ import annotations


def chunks_in_window(ctx) -> list[dict]:
    """The engine's ``serve/prefill_chunk`` events inside the trace."""
    t0, t1 = ctx.capture.t0, ctx.capture.t1
    return [r for r in ctx.records
            if r.get("name") == "serve/prefill_chunk" and t0 <= r["ts"] <= t1]


def prompt_lengths(ctx) -> dict[int, int]:
    return {r["attrs"]["uid"]: r["attrs"]["prompt_len"] for r in ctx.records
            if r.get("name") == "serve/prefill_start"}


def prefill_work(ctx) -> tuple[int, int]:
    """(prompt tokens prefilled, prompts whose last chunk ran) in the
    trace; only a prompt's last position feeds the head."""
    lens = prompt_lengths(ctx)
    tokens = finals = 0
    for r in chunks_in_window(ctx):
        a = r["attrs"]
        tokens += a["seg_len"]
        finals += int(lens.get(a["uid"]) == a["filled"])
    return tokens, finals


def decoded_tokens(ctx) -> int:
    """Tokens the decode tick produced (every token after a request's
    first) that reached the client inside the trace."""
    t0, t1 = ctx.capture.t0, ctx.capture.t1
    return sum(1 for r in ctx.requests.values()
               for t in r.times[1:] if t0 <= t <= t1)
