"""Whole-step share of the chip's peak (see vbench.readers)."""

from vbench.readers import step_mfu as read  # noqa: F401
