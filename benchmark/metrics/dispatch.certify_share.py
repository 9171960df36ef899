"""dispatch.certify_share: the share of the window inside the program's
``analysis.certify`` span (certification of one key's verdict: the
witness replay, the CPU cross-check and the differential replays):
the union of its intervals, clipped to the window, over the window.
Read from the program's spans on the device trace's clock
(``trace/attribute.py``); nothing where the run carries none."""

from benchmark.trace import attribute


def read(run):
    return attribute.span_share(run.trace, "analysis.certify")
