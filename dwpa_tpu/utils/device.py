"""The one accelerator probe every device-or-host gate shares.

A backend that fails to initialise raises out of here: a broken TPU is
an error for its caller, never read as "no accelerator" (which would
quietly move the work to the host).  jax is imported lazily so server
modules that only sometimes touch the device stay light to import.
"""


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    import jax

    return jax.devices()[0].platform == "tpu"
