"""Strips the stage-1 fan dispatched per flushed batch: the mean ``strips``
attribute of the window's ``index.fan.stage1`` spans, a count the program
makes once per segment.  Nothing to read from a program that does not
count them."""

from bench.program_spans import roots


def read(w):
    strips = [s.attrs["strips"] for r in roots()
              for s in r.find("index.fan.stage1") if "strips" in s.attrs]
    return sum(strips) / len(strips) if strips else None
