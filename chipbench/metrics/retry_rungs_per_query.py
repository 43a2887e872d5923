"""Overflow-ladder rungs climbed per executed query over the window
(``LadderTelemetry.retry_rungs`` and ``queries`` deltas)."""


def read(ctx):
    q = ctx["queries_executed"]
    return ctx["retry_rungs"] / q if q else None
