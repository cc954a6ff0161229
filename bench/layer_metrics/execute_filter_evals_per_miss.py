"""Answers a cache miss filtered anew (the ``filter_evals`` the program
records on the window's ``daisy.execute`` spans), per miss: one where every
step reuses the first step's answer, one more after each step that cleaned.
A program whose spans carry no such count gets no reading."""

from misses import misses


def read(ctx):
    spans = misses(ctx.spans)
    if spans is None or any("filter_evals" not in s.attrs for s in spans):
        return None
    return sum(s.attrs["filter_evals"] for s in spans) / len(spans)
