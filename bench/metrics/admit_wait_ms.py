"""Engine layer: mean wait from a request's due time to the moment the
engine starts its prefill (its ``serve/prefill_start`` span record), over
the requests whose prefill started inside the trace.  Moves
``ttft_p95_ms``."""


def read(ctx):
    t0, t1 = ctx.capture.t0, ctx.capture.t1
    waits = [(r["ts"] - ctx.requests[r["attrs"]["uid"]].due) * 1e3
             for r in ctx.records
             if r.get("name") == "serve/prefill_start" and t0 <= r["ts"] <= t1
             and r["attrs"]["uid"] in ctx.requests]
    return sum(waits) / len(waits) if waits else None
