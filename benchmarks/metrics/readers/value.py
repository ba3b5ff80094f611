"""A value the driver took itself on the host clock or from the
program's counters, by its key."""


def read(ctx, key):
    return ctx["values"].get(key)
