"""Device: 1 - (union of the device's operation intervals) / traced
window, averaged over the chips used (%)."""


def read(run):
    if run.trace is None or run.trace.idle_share() is None:
        return None
    return 100.0 * run.trace.idle_share()
