"""Share of the traced slice in which a verify program ran on the
device: the union of the ``XLA Modules`` intervals whose program name
holds ``verify`` (``verify_pmkid``, ``verify_eapol``, ``verify_cmac``,
``verify_assemble``; ``parallel/step.py`` names them), per chip, over
the slice, averaged over chips.  None where no program of the slice
carries such a name."""

from benchmark.spans import module_seconds, of


def read(run):
    red = of(run)
    if red is None:
        return None
    s = module_seconds(red, "verify")
    return None if s is None else s / red["window_s"]
