"""Device: the share of the traced window in which no operation ran
on the chip, in % (devtrace.idle_percent). Moves the cell's end-to-end
metric."""

import devtrace


def read(r):
    return devtrace.idle_percent(r.get("trace"))
