"""Host spans recorded from the benchmark's own files, around the calls
into each layer (choosing-metrics section 4: spans inside the program
are a later change). Installed only in a ``--trace 1`` run.

A span is ``(name, start_ns, end_ns)`` on ``time.monotonic_ns``, the
clock the generator logs on. While the profiler runs, each span is also
a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so that the
idle gaps of the device can be attributed on the trace's own clock.
"""

import functools
import time

TRACE_PREFIX = "bench:"


class Recorder:
    def __init__(self):
        self.spans = []
        self.annotate = None  # jax.profiler.TraceAnnotation while tracing

    def wrap(self, fn, name: str):
        spans, now = self.spans, time.monotonic_ns

        @functools.wraps(fn)
        def timed(*a, **kw):
            note = self.annotate
            t0 = now()
            try:
                if note is None:
                    return fn(*a, **kw)
                with note(TRACE_PREFIX + name):
                    return fn(*a, **kw)
            finally:
                spans.append((name, t0, now()))
        return timed

    def wrap_attr(self, obj, attr: str, name: str) -> bool:
        fn = getattr(obj, attr, None)
        if fn is None:
            return False
        setattr(obj, attr, self.wrap(fn, name))
        return True

    def starts(self, name: str) -> list:
        return [t0 for n, t0, _t1 in list(self.spans) if n == name]
