"""Median host time of the program's ``serve.readback`` span: copy one
micro-batch's computed logits to the host (see vbench.program_spans)."""

from vbench import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.readback")
