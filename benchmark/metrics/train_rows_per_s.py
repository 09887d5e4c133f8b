"""Training rows x passes completed in the window, over the window's
seconds: all the work over all the time, from the first piece's launch to
the last result fetched. Four chips count the rows of all four."""


def read(run):
    if run.seconds <= 0 or run.passes <= 0:
        return None
    return run.window["rows"] * run.passes / run.seconds
