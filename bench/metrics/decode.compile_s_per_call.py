"""Seconds of tracing, lowering, compiling and compile-cache loading that
JAX reports inside the window, per `generate` call."""


def read(ctx):
    calls = ctx["counters"].get("calls", 0)
    if not calls:
        return None
    return ctx["counters"]["window_compile_s"] / calls
