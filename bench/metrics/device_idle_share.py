"""Share of the traced window in which no operation ran on the chips
(averaged over them): 100 x (1 - busy union / window)."""


def read(run):
    return 100.0 * run.trace.idle_share()
