"""Device: the share of the time a query was outstanding in which nothing
ran on the device, in %: 1 minus the union of device-operation intervals
over the traced window, with the loop's waits for the next arrival (no
query outstanding) taken out of both."""


def read(rec, peak):
    t = rec.trace
    if t is None or not t["active_s"]:
        return None
    return (1.0 - t["active_busy_s"] / t["active_s"]) * 100.0
