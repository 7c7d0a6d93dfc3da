"""Share of the traced stretch in which no operation ran on the device, %.

One minus the union of the device's operation intervals (``XLA Ops`` in
the profiler trace) over the stretch, averaged over the chips."""


def read(r):
    t = r.trace
    if t is None or not t.window_s:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100
