"""Host gap per decoded token (ms): the mean, over the captured tokens,
of end(``repro.serve.dispatch`` i) - end(``repro.serve.copy`` i) within a
batch.  Once token i is on the host the device has drained; it waits
until the next decode call is dispatched.  Read from the program's
runtime spans of the traced window; a program without them reads none."""


def read(rec):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    gaps, copied = [], {}
    for r in spans.captured():
        key = (r.ids.get("batch"), r.ids.get("token"))
        if r.name == "repro.serve.copy":
            copied[key] = r.end_ns
        elif r.name == "repro.serve.dispatch" and key in copied:
            gaps.append(r.end_ns - copied.pop(key))
    return 1e-6 * sum(gaps) / len(gaps) if gaps else None
