"""dispatch.prepare_share: the share of the window inside the program's
``independent.prepare`` span (the batched path's per-key client-op
selection, segment planning and encoding): the union of its
intervals, clipped to the window, over the window. Read from the
program's spans on the device trace's clock (``trace/attribute.py``);
nothing where the run carries none."""

from benchmark.trace import attribute


def read(run):
    return attribute.span_share(run.trace, "independent.prepare")
