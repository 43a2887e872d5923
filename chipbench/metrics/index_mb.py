"""Megabytes (10^6 bytes) of the CPQx index's device arrays."""


def read(ctx):
    return ctx["index_bytes"] / 1e6
