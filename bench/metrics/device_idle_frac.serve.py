"""Share of the traced serving window in which no operation ran on the
device, averaged over the chips."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_frac()
