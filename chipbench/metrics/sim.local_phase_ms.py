"""sim.local_phase_ms: the local phase's device time a round, in ms.

The self time of every op the round program runs under the scope
``bso.local_phase`` (``core/engine.local_phase``: the scan of the
clinics' local steps, with each step's batch sampling, forward,
backward and Adam), from the device trace, over the traced rounds
(``chipbench/scopes.py``).
"""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "bso.local_phase")
