"""Mean idle gap on the device between the last op of one fit program and
the first op of the next (dispatch of one fit), from the trace."""


def read(run):
    t = run.trace
    if not t or not t["piece_gaps_s"]:
        return None
    gaps = t["piece_gaps_s"]
    return 1e3 * sum(gaps) / len(gaps)
