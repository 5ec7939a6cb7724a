"""Share of the chip's HBM bandwidth the decode program reaches (%): the
bytes a decode step has to read (``decode_bytes.py``), averaged over the
traced steps by the ``rows`` and ``pos`` of the program's
``serve.decode_step`` spans, over the device seconds per run of the
decode program in the trace, times the peak bytes per second."""
import decode_bytes


def read(run):
    """The value for one traced run, or None when there is nothing to read."""
    if run.trace is None or not run.facts:
        return None
    try:
        import repro.common.spans as spans
    except ImportError:  # a program without spans
        return None
    steps = [s.ids for s in spans.recorded()
             if s.name == "serve.decode_step" and "pos" in s.ids]
    n, device_s = run.trace.program_seconds(r"serve_step")
    if not steps or not n:
        return None
    per_step = sum(decode_bytes.step_bytes(run.cell.config, i["rows"],
                                           i["pos"]) for i in steps)
    per_step /= len(steps)
    return per_step / (device_s / n * run.peak["hbm_bytes_per_s"]) * 100.0
