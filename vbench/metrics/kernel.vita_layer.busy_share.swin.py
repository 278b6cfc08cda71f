"""Encoder-layer kernel share of device busy time (see vbench.readers)."""

from vbench.readers import vita_layer_busy_share as read  # noqa: F401
