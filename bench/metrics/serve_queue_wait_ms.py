"""Mean time an answered request waited in the frontend
(``serve/resilience.py`` admission queue and microbatching): per request
``ServeResult.latency - ServeResult.dispatch``, admission to the start of
the microbatch that served it."""


def read(ctx):
    waits = ctx.get("queue_wait_s") if ctx.get("kind") == "serve" else None
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
