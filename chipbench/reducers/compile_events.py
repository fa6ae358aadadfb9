"""Compilations JAX reported between the start of the window and its end
(``backend_compile_duration`` events; a cache hit counts).  Expected 0."""


def reduce(measured, params):
    return measured.compiles_in_window
