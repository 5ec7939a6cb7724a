"""Host milliseconds a decode step takes beyond its device time: the mean
``serve.decode_step`` span of the program (``repro.common.spans``), from
the step's dispatch to its tokens on the host, less the device
milliseconds per run of the decode program in the same traced window."""


def read(run):
    """The value for one traced run, or None when there is nothing to read."""
    if run.trace is None or not run.facts:
        return None
    try:
        import repro.common.spans as spans
    except ImportError:  # a program without spans
        return None
    ns = [s.ns for s in spans.recorded() if s.name == "serve.decode_step"]
    n, device_s = run.trace.program_seconds(r"serve_step")
    if not ns or not n:
        return None
    return sum(ns) / len(ns) / 1e6 - device_s / n * 1e3
