"""Seconds of set-up spent getting executables: JAX's backend-compile
durations (a compile, or a read of its persistent cache) plus the
seconds the program's AOT cache took to reload its artifacts."""


def read(ctx):
    return ctx["compile_s"]
