"""Host time to dispatch one decode step (ms): the mean of the program's
``repro.serve.dispatch`` spans, around the jitted decode call, in the
traced window; a program without them reads none."""


def read(rec):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    secs = [r.seconds for r in spans.captured()
            if r.name == "repro.serve.dispatch"]
    return 1e3 * sum(secs) / len(secs) if secs else None
