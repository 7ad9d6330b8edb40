"""Host spans of the round engine and the serving engine, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``fednano.<name>``. It records only while a profiler session runs
(``jax.profiler.trace(dir)`` or ``start_trace``/``stop_trace``); otherwise
it costs its constructor, about a microsecond. The profiler's own buffer
holds the spans, beside the device's operations and on the clock they
are aligned to, and writes them out when the trace stops.

Arguments are counts known when the span opens; those known only at its
end are set on the open span with ``set_metadata(**args)``. A span whose
name ends in ``.wait`` is the host blocked on the device; every other
span is host work.
"""
import jax


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``fednano.<name>`` with integer or string ``args``."""
    return jax.profiler.TraceAnnotation("fednano." + name, **args)
