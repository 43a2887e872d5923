"""Device milliseconds of the plan-walker modules (``run_plan_batch``,
``run_union_batch``) per query of the traced pass."""


def read(ctx):
    q = ctx["traced_queries"]
    s = ctx["trace"]["walker_s"]
    return 1000.0 * s / q if q and s > 0 else None
