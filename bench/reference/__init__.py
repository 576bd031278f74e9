"""Plain references the benchmark checks the program against.

Nothing here imports the program: the quadrature and the DAG
composition are written out again from their published definitions, in any float dtype, so that the same code
gives the float64 reference and the bfloat16 control.
"""

from contextlib import contextmanager


@contextmanager
def float64():
    """JAX's 64-bit mode for the references, restored on exit: the program
    that may run next in the same process was written for 32 bits."""
    import jax
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", was)
