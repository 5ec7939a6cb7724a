"""Host milliseconds from a slot's prefill dispatch to its first tokens on
the host, mean over the traced slots: the program's ``serve.prefill``
spans (``repro.common.spans``)."""


def read(run):
    """The value for one traced run, or None when there is nothing to read."""
    if not run.facts:  # nothing was served
        return None
    try:
        import repro.common.spans as spans
    except ImportError:  # a program without spans
        return None
    ns = [s.ns for s in spans.recorded() if s.name == "serve.prefill"]
    return sum(ns) / len(ns) / 1e6 if ns else None
