"""Compile requests (compiles or cache loads: either stalls the loop) issued
inside the measured window, from jax's monitoring events. Must be 0."""


def read(run):
    return float(run.window_compile_requests)
