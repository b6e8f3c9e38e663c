"""join_ms: device time per transform of the ops the program names
``pfft.join`` (f32 planes to complex: the multiply-add joins and
``X64Combine``), on the device with the most; nothing to read where the
program names no scopes or no join ran (see ``split_ms``)."""

SCOPE = "pfft.join"


def read(ctx):
    return ctx.metric("split_ms").scope_ms(ctx, SCOPE)
