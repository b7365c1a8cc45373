"""Client updates the server absorbed over the window, divided by the
window's seconds.  Absorbed: ingested, and included in an aggregation
whose dispatch has been encoded and is ready on the device."""


def read(run):
    if "absorbed" not in run.c:
        return None
    return run.c["absorbed"] / run.c["window_s"]
