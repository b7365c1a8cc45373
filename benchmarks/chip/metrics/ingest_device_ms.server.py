"""Device milliseconds of the ingest's own programs per upload: the device
time of every execution in the traced window of the row write
(``jit__write_range``), of the chunk join (``jit__join_chunks``, each a
join of up to 16 decoded chunks, ``codecs.decode_concat``) and, for delta
schemes, of ``jit__ingest_base`` and ``jit__ingest_add``, over the uploads
ingested in the window.  The server cell wraps each ``ingest_payload`` call in
one ``bench.ingest`` span, so those spans count the uploads.

A program whose join still runs as the generic ``jit_concatenate`` reads
the row write alone here: such a reading leaves out the join and is not
comparable with one that names it."""

MODULES = ("jit__write_range", "jit__join_chunks", "jit__ingest_")


def read(run):
    if run.trace is None:
        return None
    n = len(run.trace.span_seconds("bench.ingest"))
    t = run.trace.module_seconds(MODULES)
    return 1e3 * t / n if n and t else None
