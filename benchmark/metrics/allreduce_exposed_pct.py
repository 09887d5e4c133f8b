"""Time in which an all-reduce runs on a chip and no other op does, over
the runs of the fit program that the trace shows with their text; the mean
over the chips."""


def read(run):
    t = run.trace
    if not t or not t.get("collective_window_s"):
        return None
    return 100.0 * t["collective_exposed_s"] / t["collective_window_s"]
