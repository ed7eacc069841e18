"""Mean engine evaluation per microbatch in the window (``serve/engine.py``
device call plus host route and stitch): the window's share of the
``serve.engine/dispatch_s`` histogram."""


def read(ctx):
    if ctx.get("kind") != "serve" or ctx.get("dispatch_s") is None:
        return None
    return 1e3 * ctx["dispatch_s"]
