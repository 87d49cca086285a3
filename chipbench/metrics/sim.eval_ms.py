"""sim.eval_ms: eval's device time a round, in ms.

The self time of every op the round program runs under the scope
``bso.eval`` (``core/engine.eval_swarm``: each clinic's val accuracy),
from the device trace, over the traced rounds (``chipbench/scopes.py``).
"""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "bso.eval")
