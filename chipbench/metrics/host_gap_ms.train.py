"""Host gap per training step (ms): the mean, over consecutive captured
steps n and n + 1, of end(``repro.train.dispatch`` n + 1) - end(
``repro.train.sync`` n).  Once the loss of step n is on the host the device
has drained; it waits until the next step is dispatched: the fault poll,
the save decision, the data and the dispatch.  Read from the program's
runtime spans of the traced window; a program without them reads none."""


def read(rec):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    gaps, sync = [], None
    for r in spans.captured():
        if r.name == "repro.train.sync":
            sync = r
        elif (r.name == "repro.train.dispatch" and sync is not None
              and r.ids["step"] == sync.ids["step"] + 1):
            gaps.append(r.end_ns - sync.end_ns)
    return 1e-6 * sum(gaps) / len(gaps) if gaps else None
