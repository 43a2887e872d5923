"""The plan walker's share of its memory roofline, in percent.

The least work a query needs is to write its answer: 8 bytes (two int32)
per answer pair, whatever implements it.  The least time is those bytes
of the traced pass over the chip's HBM bandwidth; the share is that over
the walker modules' device time."""


def read(ctx):
    s = ctx["trace"]["walker_s"]
    if s <= 0:
        return None
    least = 8.0 * ctx["traced_answer_pairs"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / s if least > 0 else None
