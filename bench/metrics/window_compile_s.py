"""Seconds of XLA backend compiles inside the window, from JAX's
monitoring events: the program compiling shapes its warm-up could not
know, such as one slice per new survivor count."""


def read(ctx):
  return ctx["compile_s"]
