"""Median host time of the program's ``serve.wait`` span: block until one
micro-batch's logits are computed (see vbench.program_spans)."""

from vbench import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.wait")
