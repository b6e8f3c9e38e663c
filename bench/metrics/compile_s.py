"""compile_s: host clock around the first call of the plan in set-up:
trace, lower, compile or load from the persistent cache, and one
transform."""


def read(ctx):
    return ctx.counters.get("compile_s")
