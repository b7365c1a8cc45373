"""Local-training samples processed by all clients over the window,
divided by the window's seconds, through the whole FL loop: simulator,
client steps, server and evaluation."""


def read(run):
    if "samples" not in run.c:
        return None
    return run.c["samples"] / run.c["window_s"]
