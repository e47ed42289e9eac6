"""pipeline_s.train_hybrid: seconds the Myia pipeline took while set-up
compiled the hybrid's programs: the union of the ``parse``, ``ad.grad``
and ``compile_pipeline`` spans and what nests in them, less any ``xla.*``
span inside them (``pipeline_s``'s reading, on this cell's ten-layer
graph).  Source: the program's own spans (``obs.trace``)."""

from bench.metrics.pipeline_s import read  # noqa: F401
