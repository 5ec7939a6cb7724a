"""Host milliseconds of the copy of one decode step's tokens to the host
once they are ready, mean over the traced steps: the program's
``serve.fetch`` spans inside ``serve.decode_step``
(``repro.common.spans``)."""


def read(run):
    """The value for one traced run, or None when there is nothing to read."""
    if not run.facts:  # nothing was served
        return None
    try:
        import repro.common.spans as spans
    except ImportError:  # a program without spans
        return None
    ns = [s.ns for s in spans.recorded() if s.name == "serve.fetch"
          and s.parent is not None and s.parent.name == "serve.decode_step"]
    return sum(ns) / len(ns) / 1e6 if ns else None
