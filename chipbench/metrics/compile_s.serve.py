"""Compilation in the serving cell's set-up (s): the time covered by the
program's ``repro.compile`` records (jaxpr traces, lowerings, backend
compiles, persistent-cache retrievals) that end before the first span of
the traced window.  The union, not the sum: a cache retrieval lies inside
its backend compile, a nested jit's trace inside its caller's.  A program
without runtime spans reads none."""


def read(rec):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    records = spans.captured()
    starts = [r.start_ns for r in records if r.name != spans.COMPILE]
    if not starts:
        return None
    first = min(starts)
    total, end = 0, None
    for s, e in sorted((r.start_ns, r.end_ns) for r in records
                       if r.name == spans.COMPILE and r.end_ns <= first):
        if end is None or s > end:
            total, end = total + e - s, e
        elif e > end:
            total, end = total + e - end, e
    return 1e-9 * total
