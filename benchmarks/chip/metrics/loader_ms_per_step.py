"""Host time in ``LakeDataLoader.next_batch`` per train step, in ms.

The harness's span around each call, summed over the traced stretch and
divided by the steps that ran in it."""


def read(r):
    if not r.window.get("steps"):
        return None
    return r.window["loader_s"] / r.window["steps"] * 1e3
