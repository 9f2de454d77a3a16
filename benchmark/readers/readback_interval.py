"""What one token step of one program cost the streams, in ms: over the
``engine.readback`` phases of the traced run that read a token step (they
carry the count ``mixed``, the program of the step READ: 1 a mixed step that
carried a prefill chunk, 0 a decode step; the read of first tokens carries
``first`` and is left out), the median of (end of this read-back - end of
the one before it), kept where this one's ``mixed`` is the argument.  The
engine reads step N while step N+1 runs, so the interval is step N's device
time where the device is the slower, and it is the sample the engine's own
``step_latency_by_program_s`` takes.  None where no read-back carries the
count (a program older than it) or none of the asked program was read."""

from benchmark import spans, stats


def read(rc, mixed, scale=1000.0):
    found = (None if rc.trace is None
             else spans.phase_stats(["engine.readback"]))
    reads = [(end, c["mixed"]) for _, _, end, c in found or []
             if "mixed" in c]
    xs = [b[0] - a[0] for a, b in zip(reads, reads[1:]) if b[1] == mixed]
    if not xs:
        return None
    return stats.percentile(xs, 0.5) * scale
