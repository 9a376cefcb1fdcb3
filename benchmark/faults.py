"""Faults planted under the timed path, for the tests and the controls.

Each is a context manager that patches the engine of ``dwpa_tpu`` for
the length of one run; a run under any of them must read
``correct: false``.  The benchmark's own runs never use them.

- ``no_hits``: the crack step's results come back as if nothing matched
  (a step that returns its state unchanged);
- ``half_batch``: the second half of every batch's lanes is never
  checked (half of the batch left out); also the control that breaks
  the configuration's guarantee "each candidate of a unit is tried at
  least once";
- ``altered_psk``: every found PSK is altered where the engine produces
  it (an answer altered where it is produced).

The control that breaks the guarantee "NC window 8" is not a patch but
the configuration run with ``nc`` 2 (``CONTROLS``).  Which control a
cell runs, and on what planting, its traffic file's ``correctness``
block says.
"""

import contextlib
import dataclasses


@contextlib.contextmanager
def _patched_decode(wrap):
    from dwpa_tpu.models import m22000

    base = m22000.M22000Engine._decode

    def _decode(self, group, found, *args):
        return wrap(base, self, group, found, *args)

    m22000.M22000Engine._decode = _decode
    try:
        yield
    finally:
        m22000.M22000Engine._decode = base


def no_hits():
    return _patched_decode(lambda base, eng, group, found, *a: [])


def half_batch():
    def wrap(base, eng, group, found, *a):
        found = found.copy()
        found[..., found.shape[-1] // 2:] = False
        return base(eng, group, found, *a)

    return _patched_decode(wrap)


def altered_psk():
    def wrap(base, eng, group, found, *a):
        return [dataclasses.replace(f, psk=bytes([f.psk[0] ^ 1]) + f.psk[1:])
                for f in base(eng, group, found, *a)]

    return _patched_decode(wrap)


FAULTS = {"no_hits": no_hits, "half_batch": half_batch,
          "altered_psk": altered_psk}

#: Controls: each breaks one guarantee the configurations state, either
#: by a planted fault or by a configuration override.
CONTROLS = {
    "half_batch": {"fault": "half_batch"},
    "nc2": {"config": {"nc": 2}},
}
