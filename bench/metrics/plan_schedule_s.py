"""plan_schedule_s: host clock of the program's first
``pfft.plan.schedule`` span (``repro.obs``): choosing the execution
schedule in ``plan_pfft`` (wisdom, the estimate or measure tuner, or
the default).  Nothing to read where the program has no such span."""

SPAN = "pfft.plan.schedule"


def read(ctx):
    return ctx.metric("plan_partition_s").first_s(SPAN)
