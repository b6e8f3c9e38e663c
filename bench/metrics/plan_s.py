"""plan_s: host clock around ``plan_pfft`` in set-up (partition, cost
model, schedule; no device work with ``tune="estimate"``)."""


def read(ctx):
    return ctx.counters.get("plan_s")
