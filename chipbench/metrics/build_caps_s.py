"""Seconds of the host capacity estimate (``estimate_build_caps``) that
the device build needs before it can run."""


def read(ctx):
    return ctx["build_caps_s"]
