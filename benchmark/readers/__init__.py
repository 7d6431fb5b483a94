"""One reader a module: ``read(obs) -> float | None``.

``obs`` is what a driver observed in one run (``drivers/``): plain
counts, host-clock spans, the engine's own counters, the trace's
reduction. A reader that finds nothing to read returns None and the
harness leaves its metric out of the line. A metric's definition file
names its reader's module; a new reader is a new file here.
"""
