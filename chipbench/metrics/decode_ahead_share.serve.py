"""Share (%) of the captured decode dispatches that found their input
token still being computed: the ``ahead`` id of the program's
``repro.serve.dispatch`` spans in the traced window.  Where it is true the
step was queued behind the one producing its token, and the device ran
from one into the other.  A program without the spans, or whose spans
carry no ``ahead``, reads none."""


def read(rec):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    ahead = [r.ids["ahead"] for r in spans.captured()
             if r.name == "repro.serve.dispatch" and "ahead" in r.ids]
    return 100.0 * sum(ahead) / len(ahead) if ahead else None
