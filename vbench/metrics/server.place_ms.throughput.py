"""Median host time of the program's ``serve.place`` span: put one
micro-batch on the device (see vbench.program_spans)."""

from vbench import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.place")
