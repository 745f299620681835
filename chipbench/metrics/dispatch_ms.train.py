"""Host time to dispatch one training step (ms): the mean of the
program's ``repro.train.dispatch`` spans, around the jitted step's call,
in the traced window; a program without them reads none."""


def read(rec):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    secs = [r.seconds for r in spans.captured()
            if r.name == "repro.train.dispatch"]
    return 1e3 * sum(secs) / len(secs) if secs else None
