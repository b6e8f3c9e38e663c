"""plan_partition_s: host clock of the program's first
``pfft.plan.partition`` span (``repro.obs``): the row partition (FPM
partitioning, paper Algorithm 2, or the even split) and the pad lengths
in ``plan_pfft``.  Nothing to read where the program has no such span."""

SPAN = "pfft.plan.partition"


def first_s(span):
    """``first_s`` of the program's span ``span``, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot().get(span, {}).get("first_s")


def read(ctx):
    return first_s(SPAN)
