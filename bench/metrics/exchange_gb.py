"""exchange_gb: the bytes each device sends off the device in one
transform, in GB, by the program's own counter ``pfft.exchange.bytes``
(``repro.obs``: counted from the live plan's compiled executable).
Nothing to read where the program keeps no such counter: one chip, or
a program without counters."""

COUNTER = "pfft.exchange.bytes"


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    live = getattr(obs, "live_counters", None)
    value = live().get(COUNTER) if live is not None else None
    return value / 1e9 if value else None
