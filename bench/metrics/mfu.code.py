"""Model FLOPs of the traced slots (prefill and decode of real tokens,
from shapes) over the serve calls' host-clock time times the bf16 peak
(%)."""


def read(run):
    """The value for one traced run, or None when there is nothing to read."""
    f, s = run.facts.get("request_flops"), run.facts.get("call_host_s")
    if not f or not s:
        return None
    return f / (s * run.peak["flops_bf16"]) * 100.0
