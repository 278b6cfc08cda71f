"""Median host time of the program's ``serve.stack`` span: stack one
micro-batch's images and pad it to its bucket (see
vbench.program_spans)."""

from vbench import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.stack")
