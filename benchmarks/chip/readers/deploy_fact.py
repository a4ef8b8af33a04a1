"""A fact of the deployment's start-up, taken by the host clock."""


def read(ctx, key):
    return ctx["deployment"].get(key)
