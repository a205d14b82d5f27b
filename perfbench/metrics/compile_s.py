"""Seconds JAX spent in set-up tracing, lowering and compiling (or loading
compiled programs from the persistent cache), from its monitoring events."""


def read(run):
    return run.rec.phases.get("compile_s")
