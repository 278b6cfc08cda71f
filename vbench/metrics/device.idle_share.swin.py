"""Device idle share of the traced window (see vbench.readers)."""

from vbench.readers import idle_share as read  # noqa: F401
