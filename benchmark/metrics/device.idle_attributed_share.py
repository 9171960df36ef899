"""device.idle_attributed_share: the idle seconds of the window whose
piece carries a program-span label (a phase lap, a dispatch or an
analysis span covering it), over all idle seconds of the window. Read
from the program's spans on the device trace's clock
(``trace/attribute.py``); nothing where the run carries none."""

from benchmark.trace import attribute


def read(run):
    return attribute.idle_attributed_share(run.trace)
