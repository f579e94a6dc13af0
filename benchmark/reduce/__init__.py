"""From a profiler trace to numbers: interval arithmetic (``intervals``)
and the reading of an ``.xplane.pb`` (``xplane``)."""
