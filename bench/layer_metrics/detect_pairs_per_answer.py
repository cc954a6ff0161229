"""Comparison pairs the detections scanned in the window, foreground and
background (delta of ``Daisy.detect_pairs``), per answer."""


def read(ctx):
    if ctx.answers <= 0:
        return None
    m0, m1 = ctx.counters
    return (m1["detect_pairs"] - m0["detect_pairs"]) / ctx.answers
