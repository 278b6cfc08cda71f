"""Encoder-layer kernel share of its roofline (see vbench.readers)."""

from vbench.readers import vita_layer_roofline as read  # noqa: F401
