"""Set-up: from the process's start to the window's start (imports,
inputs, and compiling or loading every program shape of the cell)."""


def read(ctx):
  return ctx["setup_s"]
