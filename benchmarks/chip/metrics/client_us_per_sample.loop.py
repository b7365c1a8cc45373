"""Device microseconds of the client's jitted epoch program
(``core/client.make_epoch_fn``) per sample it trained, in the traced
window."""

MODULES = ("jit_epoch",)


def read(run):
    if run.trace is None or not run.c.get("samples"):
        return None
    t = run.trace.module_seconds(MODULES)
    return 1e6 * t / run.c["samples"] if t else None
