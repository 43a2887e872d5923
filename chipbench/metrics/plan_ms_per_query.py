"""Host milliseconds per executed query in ``Engine.plan`` and
``Engine.group_batch`` (plan, lookup ranges, capacity estimate, buckets),
from the harness's spans over the window."""


def read(ctx):
    q = ctx["queries_executed"]
    s = ctx["spans"]
    if not q or "plan" not in s:
        return None
    return 1000.0 * (s["plan"] + s.get("group_batch", 0.0)) / q
