"""Python collections per micro-batch dispatched: the program's
``host.gc`` spans over its ``serve.dispatch`` spans (see
vbench.program_spans)."""

from vbench.program_spans import gc_per_batch as read  # noqa: F401
