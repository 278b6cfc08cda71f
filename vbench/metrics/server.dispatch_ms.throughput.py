"""Host time per micro-batch dispatch (see vbench.readers)."""

from vbench.readers import dispatch_ms as read  # noqa: F401
