"""Names by which the trace readers find kernels among the device ops.

The PBKDF2 Pallas kernel shows in the ``XLA Ops`` line of a v5e trace as
``%pbkdf2_sha1_pmk_pallas.<n> = ... custom-call(...)``, after the name
``ops/pbkdf2_pallas.py`` gives its ``pallas_call`` (my chip run, PR 22).
"""

PBKDF2 = ("pbkdf2_sha1_pmk_pallas",)
